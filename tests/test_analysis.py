"""repro.analysis — static lint + HLO auditor tests.

Layer 1: one true-positive AND one true-negative fixture per AST rule
(the negative pins the false-positive fixes: static_argnames, kwonly
kernel statics, ``.shape`` metadata, 'float64' outside dtype position),
registry-completeness rules against both the live repo (clean) and a
synthetic broken repo (every rule fires), and the ratchet baseline
round trip.

Layer 2: the expectation table and ``check_text`` on synthetic HLO
(fast), the recompile-hazard mirror, and — marked slow — real
lowerings: the single-device smoke audit end to end and a subprocess
f64 injection under ``JAX_ENABLE_X64=1`` that the auditor must catch.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (ALL_RULES, REPO_RULES, RULES, Finding, compare,
                            lint_repo, lint_source, load_baseline,
                            save_baseline)

ROOT = pathlib.Path(__file__).resolve().parents[1]


def rules_of(src: str, **kw) -> set[str]:
    return {f.rule for f in lint_source(textwrap.dedent(src), **kw)}


# ------------------------------------------------------------ rule fixtures
def test_tracer_item_inside_jit():
    assert "tracer-item" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            return x.item()
    """)


def test_tracer_item_outside_jit_is_clean():
    assert "tracer-item" not in rules_of("""
        def f(x):
            return x.item()
    """)


def test_tracer_item_in_jit_wrapped_function():
    # f2 = jax.jit(f) marks f's body as a jit context too
    assert "tracer-item" in rules_of("""
        import jax
        def f(x):
            return x.item()
        f2 = jax.jit(f)
    """)


def test_tracer_host_cast_inside_jit():
    assert "tracer-host-cast" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            return float(x) + 1.0
    """)


def test_host_cast_of_static_argnames_is_clean():
    # the repo's kernel-dispatch idiom: int(min(...)) over statics
    assert "tracer-host-cast" not in rules_of("""
        import functools, jax
        @functools.partial(jax.jit, static_argnames=("k", "block"))
        def f(x, k, block):
            tile = int(min(k, block))
            return x * tile
    """)


def test_host_cast_of_shape_metadata_is_clean():
    # shapes are static under jit — .shape/.ndim/len() are not tracers
    assert "tracer-host-cast" not in rules_of("""
        import jax
        @jax.jit
        def f(x):
            n = int(x.shape[0])
            return x * n * len(x.shape)
    """)


def test_tracer_np_call_inside_jit():
    assert "tracer-np-call" in rules_of("""
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return np.asarray(x)
    """)


def test_np_call_on_untraced_value_is_clean():
    assert "tracer-np-call" not in rules_of("""
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return x + np.arange(4)
    """)


def test_prng_unseeded_legacy_and_seedless():
    src = """
        import numpy as np
        a = np.random.rand(4)
        rng = np.random.default_rng()
    """
    findings = [f for f in lint_source(textwrap.dedent(src))
                if f.rule == "prng-unseeded"]
    assert len(findings) == 2


def test_prng_seeded_default_rng_is_clean():
    assert "prng-unseeded" not in rules_of("""
        import numpy as np
        rng = np.random.default_rng(0)
        x = rng.standard_normal(4)
    """)


def test_prng_key_reuse():
    assert "prng-key-reuse" in rules_of("""
        import jax
        def f():
            key = jax.random.PRNGKey(0)
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
    """)


def test_prng_key_split_is_clean():
    assert "prng-key-reuse" not in rules_of("""
        import jax
        def f():
            key = jax.random.PRNGKey(0)
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (3,))
            b = jax.random.uniform(k2, (3,))
            return a + b
    """)


def test_f64_dtypeless_constructor():
    assert "f64-dtypeless" in rules_of("""
        import jax.numpy as jnp
        x = jnp.zeros((4,))
    """)


def test_f64_dtypeless_gated_by_hot_path():
    src = """
        import jax.numpy as jnp
        x = jnp.zeros((4,))
    """
    assert "f64-dtypeless" not in rules_of(src, hot_path=False)


def test_explicit_dtype_constructor_is_clean():
    assert "f64-dtypeless" not in rules_of("""
        import jax.numpy as jnp
        x = jnp.zeros((4,), jnp.float32)
        y = jnp.ones((4,), dtype=jnp.int32)
    """)


def test_f64_explicit_dtype_and_astype():
    src = """
        import numpy as np
        a = np.zeros(3, dtype=np.float64)
        b = a.astype("float64")
        c = a.astype(float)
    """
    findings = [f for f in lint_source(textwrap.dedent(src))
                if f.rule == "f64-explicit"]
    assert len(findings) == 3


def test_f64_string_outside_dtype_position_is_clean():
    # the lint rule's own description mentions 'float64' — message
    # strings and docstrings must not trip the rule
    assert "f64-explicit" not in rules_of("""
        MSG = "hot paths must not use float64"
        def f():
            '''never emit float64 here'''
            return MSG
    """)


_KERNEL_SRC = """
    import jax
    from jax.experimental import pallas as pl
    def kern(x_ref, o_ref):
        v = x_ref[...]
        %s
        o_ref[...] = v
    @jax.jit
    def call(x, n):
        return pl.pallas_call(kern, grid=%s)(x)
"""


def test_pallas_python_branch_on_tracer():
    src = _KERNEL_SRC % ("if v.sum() > 0:\n            v = -v", "(4,)")
    assert "pallas-python-branch" in rules_of(src)


def test_pallas_branch_on_kwonly_static_is_clean():
    assert "pallas-python-branch" not in rules_of("""
        import functools
        from jax.experimental import pallas as pl
        def kern(x_ref, o_ref, *, flip):
            v = x_ref[...]
            if flip:
                v = -v
            o_ref[...] = v
        def call(x):
            return pl.pallas_call(
                functools.partial(kern, flip=True))(x)
    """)


def test_pallas_nonstatic_grid():
    # grid built from a traced (dynamic) parameter
    src = _KERNEL_SRC % ("pass", "(n,)")
    assert "pallas-nonstatic-grid" in rules_of(src)


def test_pallas_grid_from_shape_or_static_is_clean():
    assert "pallas-nonstatic-grid" not in rules_of("""
        import functools, jax
        from jax.experimental import pallas as pl
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        @functools.partial(jax.jit, static_argnames=("n",))
        def call(x, n):
            return pl.pallas_call(kern, grid=(n, x.shape[0]))(x)
    """)


def test_rule_catalogue_is_complete_and_disjoint():
    assert not set(RULES) & set(REPO_RULES)
    assert ALL_RULES == {**RULES, **REPO_RULES}
    for name, doc in ALL_RULES.items():
        assert doc, f"rule {name} has no description"


# ------------------------------------------------------------ registry rules
def test_live_repo_registries_are_complete():
    """Kernel oracles, spec sections, topology snapshot arms: the live
    repo must be clean (this is the invariant `make lint` ratchets)."""
    assert lint_repo(ROOT) == []


def _write(root: pathlib.Path, rel: str, text: str) -> None:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(text))


def test_registry_rules_fire_on_broken_repo(tmp_path):
    _write(tmp_path, "src/repro/kernels/ops.py", """
        def sddmm(a, b):
            return a @ b
    """)
    _write(tmp_path, "src/repro/kernels/ref.py", "")
    _write(tmp_path, "tests/test_kernel_parity.py", "")
    _write(tmp_path, "src/repro/api/spec.py", """
        class OrphanCfg:
            pass
        _SECTIONS = {}
    """)
    _write(tmp_path, "src/repro/memory/topology.py", """
        register_topology(TierTopology("ghost", fast=None, slow=None))
    """)
    got = {f.rule for f in lint_repo(tmp_path)}
    assert got == set(REPO_RULES)


def test_registry_rules_skip_missing_surfaces(tmp_path):
    assert lint_repo(tmp_path) == []


# ------------------------------------------------------------ ratchet
_BAD = """
    import jax
    @jax.jit
    def f(x):
        return x.item()
"""


def test_baseline_round_trip(tmp_path):
    findings = lint_source(textwrap.dedent(_BAD), path="src/a.py")
    assert findings
    path = tmp_path / "baseline.json"
    save_baseline(path, findings)
    new, stale = compare(findings, load_baseline(path))
    assert new == [] and stale == []


def test_new_finding_fails_against_baseline(tmp_path):
    path = tmp_path / "baseline.json"
    save_baseline(path, [])
    findings = lint_source(textwrap.dedent(_BAD), path="src/a.py")
    new, stale = compare(findings, load_baseline(path))
    assert [f.rule for f in new] == ["tracer-item"] and stale == []


def test_fixed_finding_goes_stale(tmp_path):
    """The ratchet: a baselined violation that disappears must be
    removed from the baseline (stale entries fail too)."""
    findings = lint_source(textwrap.dedent(_BAD), path="src/a.py")
    path = tmp_path / "baseline.json"
    save_baseline(path, findings)
    new, stale = compare([], load_baseline(path))
    assert new == []
    assert [(rec, rem) for _, rec, rem in stale] == [(1, 0)]


def test_fingerprint_survives_line_shifts():
    a = lint_source(textwrap.dedent(_BAD), path="src/a.py")
    shifted = "# header\n\n\n" + textwrap.dedent(_BAD)
    b = lint_source(shifted, path="src/a.py")
    assert [f.key() for f in a] == [f.key() for f in b]
    assert a[0].line != b[0].line


def test_committed_baseline_matches_current_findings():
    """tools/lint.py must exit 0 against the committed baseline — the
    same gate CI runs."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "lint.py"),
         "--check-baseline"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------------------ HLO layer
def test_check_text_flags_f64_and_host_transfer():
    from repro.analysis.hlo_audit import check_text
    assert check_text("add.1 = f64[4,8] add(...)") != []
    assert check_text("ROOT t = c128[2] tuple(...)") != []
    assert check_text("custom-call(...), custom_call_target="
                      "\"MoveToHost\"") != []
    assert check_text("buffer: f32[4]{0:S(5)}") != []
    assert check_text("annotate_device_placement(...)") != []
    assert check_text("add.1 = f32[4,8] add(...)") == []


def test_check_text_respects_expectation_table():
    from repro.analysis.hlo_audit import check_text, expect
    int8 = "f32[4] all-reduce(...) convert s32[4] s8[4]"
    assert check_text(int8, expect("grad-combine@int8")) == []
    assert check_text("f32[4] add(...)",
                      expect("grad-combine@int8")) != []
    assert check_text("f32[4] all-reduce(...)",
                      expect("single-device")) != []
    assert check_text("f32[4] add(...)", expect("single-device")) == []


def test_expectation_merge_contains_wins_over_absent():
    from repro.analysis.hlo_audit import FRAGMENTS
    merged = FRAGMENTS["single-device"].merged(FRAGMENTS["grad-psum"])
    assert "all-reduce" in merged.contains
    assert "all-reduce" not in merged.absent
    assert "collective-permute" in merged.absent


def test_expectation_for_maps_config_to_fragments():
    from repro.analysis.hlo_audit import COLLECTIVES, expectation_for
    single = expectation_for(n_shards=1)
    assert set(single.absent) == set(COLLECTIVES)
    sharded = expectation_for(n_shards=4)
    assert {"collective-permute", "all-reduce"} <= set(sharded.contains)
    int8 = expectation_for(n_shards=4, grads="int8")
    assert {"s32", "all-reduce",
            "collective-permute"} <= set(int8.contains)
    topk = expectation_for(n_shards=4, grads="topk")
    assert "all-gather" in topk.contains


def test_assert_clean_raises_with_violation_text():
    from repro.analysis.hlo_audit import assert_clean, expect
    with pytest.raises(AssertionError, match="forbidden op"):
        assert_clean("f32[4] all-reduce(...)", expect("single-device"),
                     where="unit")


class _FakePlan:
    global_microbatch = 16

    def microbatches_for_epoch(self, epoch):
        return 1 + epoch          # warm-up grows the COUNT, not the shape


def test_recompile_hazard_engine_feed_is_single_shape():
    from repro.analysis.hlo_audit import recompile_hazard
    assert recompile_hazard(_FakePlan()) == [16]


def test_recompile_hazard_catches_ragged_direct_feed():
    from repro.analysis.hlo_audit import recompile_hazard
    shapes = recompile_hazard(_FakePlan(), batches=[16, 40])
    assert shapes == [8, 16]      # 40 = 2x16 + ragged 8 -> extra trace


# ------------------------------------------------------------ slow: lowerings
@pytest.mark.slow
def test_smoke_audit_single_device_is_clean():
    """The full Layer 2 pass on the single-device smoke preset: train
    halves, fused serve, recompile hazard."""
    from repro.analysis.hlo_audit import smoke_audit
    assert smoke_audit(mesh=1) == []


@pytest.mark.slow
def test_auditor_catches_seeded_f64_injection():
    """Self-test from the acceptance criteria: enable x64 in a
    subprocess, lower a train-step-shaped function that widens one
    intermediate to f64, and the auditor must flag it (without x64 JAX
    silently downcasts, which is why this runs out of process)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.analysis.hlo_audit import check_text

        @jax.jit
        def step(x):
            acc = x.astype(jnp.float64)      # the seeded injection
            return (acc * acc).sum().astype(jnp.float32)

        txt = step.lower(jnp.ones((8,), jnp.float32)).compile().as_text()
        v = check_text(txt, where="f64-injection")
        assert v and "f64" in v[0], f"auditor missed the injection: {v}"

        clean = jax.jit(lambda x: (x * x).sum())
        txt = clean.lower(jnp.ones((8,), jnp.float32)).compile().as_text()
        assert check_text(txt) == []
        print("F64_CAUGHT")
    """)
    env = dict(os.environ, JAX_ENABLE_X64="1",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    assert "F64_CAUGHT" in proc.stdout


@pytest.mark.slow
def test_smoke_audit_forced_mesh_is_clean():
    """The mesh=4 + int8-psum arm end to end in a forced-device
    subprocess (the same arm `make audit` runs)."""
    code = ("from repro.analysis.hlo_audit import smoke_audit\n"
            "v = smoke_audit(mesh=4, grads='int8')\n"
            "assert v == [], v\n"
            "print('MESH_AUDIT_OK')\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    assert "MESH_AUDIT_OK" in proc.stdout
