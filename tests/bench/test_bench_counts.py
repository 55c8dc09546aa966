"""The benchmark's count functions and peak table, pinned by hand."""
import pytest

from bench import counts, peaks

# lightgcn-m25: the graph of graph_seed 0 has 4,194,228 edges
NU, NI, E, D, L, B = 349_184, 53_248, 4_194_228, 128, 3, 150_528


def test_spmm_call_bytes_at_lightgcn_m25():
    u2i = 349_184 * 512 + E * 4 + 53_249 * 4 + 53_248 * 512
    i2u = 53_248 * 512 + E * 4 + 349_185 * 4 + 349_184 * 512
    assert counts.spmm_call_bytes(NU, NI, E, D) == u2i == 223_035_092
    assert counts.spmm_call_bytes(NI, NU, E, D) == i2u == 224_218_836
    assert counts.spmm_step_bytes(NU, NI, E, D, L) == 6 * (u2i + i2u)
    assert counts.spmm_edge_row_bytes(E, D) == E * 512


def test_step_flops_at_lightgcn_m25():
    n = NU + NI
    want = (12 * 2 * E * D          # 12 SpMM passes
            + 20 * n * D            # (6L + 2) N d node-level work
            + 30 * B * D            # BPR forward and backward
            + 14 * n * D)           # Adam
    assert counts.step_flops(NU, NI, E, D, L, B) == want
    assert want == 15_214_080_000


def test_peaks_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
