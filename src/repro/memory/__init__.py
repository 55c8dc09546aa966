"""repro.memory — the declarative memory-tier subsystem.

Three layers, replacing the hardcoded constants + advisory placement of
``core.tiered_memory`` (now a deprecation shim):

  ``TierTopology`` / ``Tier``  — a declarative, registered description
      of the memory system (``tpu-hbm-host``, the paper's
      ``dram-optane-appdirect`` / ``dram-optane-memorymode``,
      ``uniform`` for CPU CI);
  ``PlacementPolicy`` registry — greedy knapsack, exact DP certifier,
      the paper's §6 ``paper-recipe`` pins, all-fast/all-slow
      baselines, selected by name;
  ``TieredExecutor``           — makes the plan real on every backend
      (JAX memory kinds on TPU, a host byte store + streaming
      fetch/commit elsewhere), with the ``HostResident`` row-granular
      gather facade for serving.

The Experiment API surface is ``repro.api.MemoryCfg``; the planner
entry is ``repro.pipeline.plan.build_train_plan``.
"""
from repro.memory.cache import CacheStats, HotRowCache
from repro.memory.executor import (HostResident, QuantizedHostResident,
                                   TieredExecutor, like_placement,
                                   memory_kind_sharding, on_device)
from repro.memory.policies import (Placement, PlacementPolicy, Plan,
                                   get_policy, place_exact, place_greedy,
                                   policy_names, register_policy)
from repro.memory.profiles import (AccessProfile, gnn_recsys_profiles,
                                   quantized_table_bytes)
from repro.memory.topology import (Tier, TierTopology, get_topology,
                                   register_topology, resolve_tier,
                                   topology_names)

__all__ = [
    "Tier", "TierTopology", "get_topology", "register_topology",
    "topology_names", "resolve_tier",
    "AccessProfile", "gnn_recsys_profiles", "quantized_table_bytes",
    "Placement", "Plan", "PlacementPolicy", "get_policy",
    "register_policy", "policy_names", "place_greedy", "place_exact",
    "TieredExecutor", "HostResident", "QuantizedHostResident",
    "memory_kind_sharding", "on_device", "like_placement",
    "HotRowCache", "CacheStats",
]
