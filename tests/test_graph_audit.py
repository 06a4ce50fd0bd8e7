"""Graph auditor (repro.analysis pillar 1): text rules on synthetic HLO,
tiny jitted functions with known graph properties, golden baselines, and
the donation contract with checkpointing."""
import json
import os
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import rules_graph
from repro.launch.hlo_analysis import analyze

HERE = os.path.dirname(__file__)
GOLDENS = os.path.join(HERE, "goldens")
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))

# --------------------------------------------------------------------------
# synthetic-HLO unit tests (no lowering)
# --------------------------------------------------------------------------

DONATED_HEADER = ("HloModule jit_step, input_output_alias={ {0}: (0, {}, "
                  "may-alias), {1}: (1, {}, may-alias) }, "
                  "entry_computation_layout={(f32[4]{0})->f32[4]{0}}\n")

CALLBACK_HLO = """HloModule jit_cb

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %cc = f32[4]{0} custom-call(%p), custom_call_target="xla_ffi_python_cpu_callback"
}
"""

ALLREDUCE_HLO = """HloModule jit_ar

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %ar = f32[8]{0} all-reduce(%p), to_apply=%add
}
"""


def test_donated_params_parses_alias_header():
    assert rules_graph.donated_params(DONATED_HEADER) == {0, 1}
    assert rules_graph.donated_params("HloModule jit_f\n") == set()


def test_check_donation_failure_message():
    fails = rules_graph.check_donation("HloModule jit_f\n", min_params=2)
    assert len(fails) == 1 and "GA002" in fails[0]
    assert rules_graph.check_donation(DONATED_HEADER, min_params=2) == []


def test_find_f64_lines():
    text = "ENTRY %e (p: f64[4]) -> f64[4] {\n  %p = f64[4]{0} parameter(0)\n"
    hits = rules_graph.find_f64(text)
    assert [ln for ln, _ in hits] == [1, 2]
    assert rules_graph.find_f64(CALLBACK_HLO) == []


def test_find_host_callbacks_synthetic():
    hits = rules_graph.find_host_callbacks(CALLBACK_HLO)
    assert len(hits) == 1 and "xla_ffi_python_cpu_callback" in hits[0][1]
    assert rules_graph.find_host_callbacks(ALLREDUCE_HLO) == []


def test_collective_census_and_diff():
    census = rules_graph.collective_census(ALLREDUCE_HLO)
    assert census["collective_counts"] == {"all-reduce": 1}
    assert rules_graph.diff_census(census, census) == []
    drifted = {"collective_counts": {"all-reduce": 2}}
    fails = rules_graph.diff_census(drifted, census)
    assert len(fails) == 1 and "2 != golden 1" in fails[0]
    # a NEW collective kind is drift too
    fails = rules_graph.diff_census(
        {"collective_counts": {"all-reduce": 1, "all-gather": 1}}, census)
    assert any("all-gather" in f for f in fails)


def test_audit_text_combines_rules():
    facts, fails = rules_graph.audit_text(CALLBACK_HLO, train=True,
                                          min_donated=1)
    assert any("GA002" in f for f in fails)       # no alias header
    assert any("GA003" in f for f in fails)       # python callback
    assert facts["host_callbacks"]
    facts, fails = rules_graph.audit_text(ALLREDUCE_HLO, train=False)
    assert fails == []
    assert facts["collective_counts"] == {"all-reduce": 1}


# --------------------------------------------------------------------------
# tiny REAL jitted functions with known HLO properties
# --------------------------------------------------------------------------

def test_f64_leak_detected_in_real_lowering():
    with jax.enable_x64(True):
        text = jax.jit(lambda x: x.astype(jnp.float64) * 2).lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    assert rules_graph.find_f64(text)
    clean = jax.jit(lambda x: x * 2).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    assert rules_graph.find_f64(clean) == []


def test_donation_detected_in_real_lowering():
    x = jax.ShapeDtypeStruct((8,), jnp.float32)

    def f(a, b, c):
        return a + 1.0, b * 2.0, jnp.sum(c)

    plain = jax.jit(f).lower(x, x, x).compile().as_text()
    assert rules_graph.donated_params(plain) == set()
    donated = jax.jit(f, donate_argnums=(0, 1)).lower(
        x, x, x).compile().as_text()
    assert rules_graph.donated_params(donated) == {0, 1}
    assert rules_graph.check_donation(donated, min_params=2) == []


def test_host_callback_detected_in_real_lowering():
    def f(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    assert rules_graph.find_host_callbacks(text), \
        "pure_callback should surface as a host custom-call"


def test_retrace_guard_cache_size():
    calls = []

    @jax.jit
    def f(x):
        calls.append(1)
        return x * 2

    f(jnp.zeros(4))
    f(jnp.ones(4))
    assert f._cache_size() == 1          # same shape: one trace
    f(jnp.zeros(8))
    assert f._cache_size() == 2          # new shape: one more


def test_fused_kernel_dtype_discipline():
    from repro.analysis.graph_audit import check_fused_dtypes
    assert check_fused_dtypes() == []


# --------------------------------------------------------------------------
# hlo_analysis: fusion-body bytes come from call-site structure, not
# computation names (regression for the dead "fused"-name set)
# --------------------------------------------------------------------------

FUSION_HLO = """HloModule t

%my_body (x: f32[100]) -> f32[100] {
  %x = f32[100]{0} parameter(0)
  ROOT %y = f32[100]{0} add(%x, %x)
}

ENTRY %e (p: f32[100]) -> f32[100] {
  %p = f32[100]{0} parameter(0)
  ROOT %f = f32[100]{0} fusion(%p), kind=kLoop, calls=%my_body
}
"""

NAMED_FUSED_HLO = """HloModule t2

ENTRY %fused_main (p: f32[10]) -> f32[10] {
  %p = f32[10]{0} parameter(0)
  ROOT %y = f32[10]{0} add(%p, %p)
}
"""


def test_fusion_bytes_counted_at_call_site_only():
    # interior add (3 x 400B) must NOT be counted — only the fusion call
    # site's operand + output (2 x 400B), regardless of the body's name
    assert analyze(FUSION_HLO)["bytes_accessed"] == 800.0


def test_fused_name_substring_is_not_special():
    # a computation whose NAME contains "fused" but that is the entry
    # (not reached via calls=) keeps its bytes: 2 operands + output
    assert analyze(NAMED_FUSED_HLO)["bytes_accessed"] == 120.0


# --------------------------------------------------------------------------
# goldens: present, well-formed, and drift fails
# --------------------------------------------------------------------------

def test_goldens_exist_for_two_arch_mesh_pairs():
    from repro.analysis.graph_audit import GOLDEN_TARGETS, golden_path
    assert len(GOLDEN_TARGETS) >= 2
    for name in GOLDEN_TARGETS:
        path = golden_path(name, GOLDENS)
        assert os.path.exists(path), f"missing golden {path}"
        with open(path) as f:
            doc = json.load(f)
        assert doc["target"] == name
        # mesh graphs must actually communicate
        assert sum(doc["collective_counts"].values()) > 0
        assert rules_graph.diff_census(doc, doc) == []


def test_golden_drift_is_a_failure():
    from repro.analysis.graph_audit import GOLDEN_TARGETS, golden_path
    with open(golden_path(GOLDEN_TARGETS[0], GOLDENS)) as f:
        golden = json.load(f)
    drifted = json.loads(json.dumps(golden))
    kind = next(iter(drifted["collective_counts"]))
    drifted["collective_counts"][kind] += 1
    assert rules_graph.diff_census(drifted, golden)


# --------------------------------------------------------------------------
# GA008: resource census + goldens (flops / bytes moved / peak memory)
# --------------------------------------------------------------------------

def test_resource_census_extracts_compiled_cost():
    r = rules_graph.resource_census(FUSION_HLO, peak_bytes=1234.0)
    assert r["bytes_accessed"] == 800.0
    assert r["flops"] >= 0.0
    assert r["peak_bytes"] == 1234.0
    assert rules_graph.resource_census(FUSION_HLO)["peak_bytes"] is None


def test_diff_resources_gates_both_directions():
    golden = {"flops": 1000.0, "bytes_accessed": 5000.0,
              "peak_bytes": 100.0}
    assert rules_graph.diff_resources(dict(golden), golden) == []
    # 4% drift sits inside the default 5% tolerance
    ok = {"flops": 1040.0, "bytes_accessed": 5000.0, "peak_bytes": 100.0}
    assert rules_graph.diff_resources(ok, golden) == []
    up = {"flops": 2000.0, "bytes_accessed": 5000.0, "peak_bytes": 100.0}
    fails = rules_graph.diff_resources(up, golden)
    assert len(fails) == 1 and "GA008" in fails[0] \
        and "regressed" in fails[0]
    # an IMPROVEMENT beyond tolerance also forces a golden refresh
    down = {"flops": 1000.0, "bytes_accessed": 2000.0, "peak_bytes": 100.0}
    fails = rules_graph.diff_resources(down, golden)
    assert len(fails) == 1 and "improved" in fails[0]


def test_diff_resources_ungated_and_unmeasurable_keys():
    # golden without peak_bytes (None/missing/0): key is not gated
    golden = {"flops": 1000.0, "bytes_accessed": 5000.0,
              "peak_bytes": None}
    actual = {"flops": 1000.0, "bytes_accessed": 5000.0,
              "peak_bytes": 999999.0}
    assert rules_graph.diff_resources(actual, golden) == []
    # golden HAS a value the current backend can't measure: that's drift
    golden["peak_bytes"] = 100.0
    actual["peak_bytes"] = None
    fails = rules_graph.diff_resources(actual, golden)
    assert len(fails) == 1 and "unmeasurable" in fails[0]


def test_resource_goldens_exist_for_three_graphs():
    from repro.analysis.graph_audit import RESOURCE_TARGETS, resource_path
    assert len(RESOURCE_TARGETS) >= 3
    for name in RESOURCE_TARGETS:
        path = resource_path(name, GOLDENS)
        assert os.path.exists(path), f"missing resource golden {path}"
        with open(path) as f:
            doc = json.load(f)
        assert doc["target"] == name
        # a real compiled graph moves bytes and does work
        assert doc["flops"] > 0 and doc["bytes_accessed"] > 0
        assert rules_graph.diff_resources(doc, doc) == []


def test_resource_golden_drift_is_a_failure():
    from repro.analysis.graph_audit import RESOURCE_TARGETS, resource_path
    with open(resource_path(RESOURCE_TARGETS[0], GOLDENS)) as f:
        golden = json.load(f)
    drifted = json.loads(json.dumps(golden))
    drifted["flops"] *= 1.5
    fails = rules_graph.diff_resources(drifted, golden)
    assert fails and all("GA008" in f for f in fails)


# --------------------------------------------------------------------------
# donation contract with checkpointing (checkpoint/io.py "assumes
# donation" — make the assumption real)
# --------------------------------------------------------------------------

def test_checkpoint_copies_out_before_donation(tmp_path):
    from repro.checkpoint.io import load_train_state, save_train_state

    params = {"w": jnp.arange(4.0)}
    state = {"m": jnp.zeros(4)}

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s):
        return (jax.tree.map(lambda x: x + 1, p),
                jax.tree.map(lambda x: x + 2, s))

    p1, s1 = step(params, state)
    assert params["w"].is_deleted(), "donation did not engage"
    save_train_state(str(tmp_path), p1, s1, step=1)
    # donate the very buffers the checkpoint was saved from: if save did
    # NOT copy to host eagerly, the reload below would see garbage
    p2, s2 = step(p1, s1)
    assert p1["w"].is_deleted()
    pl, sl, start = load_train_state(str(tmp_path), p2, s2)
    assert start == 1
    np.testing.assert_allclose(np.asarray(pl["w"]), np.arange(4.0) + 1)
    np.testing.assert_allclose(np.asarray(sl["m"]), np.zeros(4) + 2)


# --------------------------------------------------------------------------
# end-to-end: the CLI on real step graphs (own process for device flags)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_graph_audit_cli_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)           # module sets its own device count
    report = tmp_path / "report.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.graph_audit",
         "--targets", "lstm-asr__nomesh,lstm-asr__mesh4x2",
         "--report", str(report)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(report.read_text())
    facts = doc["targets"]["lstm-asr__mesh4x2"]
    assert facts["donated_params"]
    assert facts["collective_counts"].get("all-reduce", 0) > 0
    assert doc["targets"]["lstm-asr__nomesh"]["f64_sites"] == 0
