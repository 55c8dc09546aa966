"""Distributed-training utilities.

  hints      — ambient sharding hints (dp/tp axis names) that model code
               reads to pin intermediates without threading a mesh
               through every call;
  ring_spmm  — node-sharded SpMM over a device ring (the gather-SpMM
               kernel on each edge bucket, collective-permute instead
               of GSPMD all-gather);
  subgraph   — the DistDGL-style subgraph-training baseline the paper
               compares single-machine full-graph training against.
"""
