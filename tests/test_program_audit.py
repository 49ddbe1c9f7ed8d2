"""Program auditor (raft_tpu.analysis.program) — ISSUE 12 acceptance.

Two speed tiers:

* **Fast** (default): walker recursion through every staging primitive,
  and a positive + negative unit test per pass over hand-built jitted
  fixtures — tracing only, no index builds, no device dispatch.
* **Slow** (``@pytest.mark.slow``, run by ``ci/run.sh test``; the gate
  itself runs as ``ci/run.sh programs``): the full registry audit over
  the toy world — every committed ``program_contracts.json`` entry
  pinned to a live program (stale entries fail, the jaxlint-baseline
  ratchet), the seeded regressions (DCN merge forced onto an f32
  allgather; a serving dispatch with donation dropped) flipping the
  gate red, and the CLI's JSON schema parity with the jaxlint CLI.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import compat
from raft_tpu.analysis.program import (
    ProgramRecord,
    aval_bytes,
    run_passes,
    walk_jaxpr,
)
from raft_tpu.analysis.program.contracts import (
    check_drift,
    load_contracts,
)
from raft_tpu.analysis.program.passes import (
    ALL_PASSES,
    collective_census,
    donation_check,
    dtype_flow,
    materialization_model,
    program_count,
)
from raft_tpu.analysis.program.registry import (
    donated_leaves,
    flip_census,
    record_from_traced,
)

REPO = Path(__file__).resolve().parent.parent
CONTRACTS = REPO / "ci" / "checks" / "program_contracts.json"


def record_of(fn, *args, meta=None, donated=None, count=None, name="t"):
    """Trace a plain function under jit into a ProgramRecord."""
    traced = jax.jit(fn).trace(*args)
    return ProgramRecord(
        name=name, jaxpr=traced.jaxpr, meta=meta or {},
        donated=donated, program_count=count,
    )


def rules_of(findings):
    return sorted({f.rule for f in findings})


# -- walker ------------------------------------------------------------------


def test_walker_recurses_scan_cond_and_marks_loop_context():
    def f(xs, p):
        def step(c, x):
            return c + jnp.sum(x @ x.T), None
        tot, _ = lax.scan(step, 0.0, xs)
        return lax.cond(p, lambda y: y * 2, lambda y: y + 1, tot)

    rec = record_of(f, jnp.ones((4, 8, 8)), True)
    sites = list(walk_jaxpr(rec.jaxpr))
    prims = {s.prim for s in sites}
    assert "scan" in prims and "cond" in prims
    # the matmul inside the scan body is visited, with loop context
    dots = [s for s in sites if s.prim == "dot_general"]
    assert dots and all(s.in_scan for s in dots)
    # the cond branches are walked but are NOT loop bodies
    branch_ops = [s for s in sites if "cond" in s.path]
    assert branch_ops and not any(s.in_scan for s in branch_ops)


def test_walker_recurses_shard_map_and_while(mesh8):
    del mesh8  # devices provisioned by conftest
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), ("dcn", "ici")
    )
    from jax.sharding import PartitionSpec as P

    def body(x):
        y = lax.psum(x, "ici")

        def w_cond(c):
            return jnp.sum(c) < 100.0

        def w_body(c):
            return c * 2.0

        return lax.while_loop(w_cond, w_body, y)

    sm = compat.shard_map(body, mesh=mesh, in_specs=P("dcn"),
                          out_specs=P("dcn"), check_vma=False)
    rec = record_of(sm, jnp.ones((8, 4)))
    sites = list(walk_jaxpr(rec.jaxpr))
    prims = {s.prim for s in sites}
    assert "shard_map" in prims and "psum" in prims and "while" in prims
    mults = [s for s in sites if s.prim == "mul"]
    assert mults and all(s.in_scan for s in mults)  # while == loop body


def test_aval_bytes():
    def f(x):
        return x.astype(jnp.bfloat16)

    rec = record_of(f, jnp.ones((4, 8), jnp.float32))
    (site,) = [s for s in walk_jaxpr(rec.jaxpr)
               if s.prim == "convert_element_type"]
    assert aval_bytes(site.eqn.outvars[0].aval) == 4 * 8 * 2
    assert aval_bytes(site.eqn.invars[0].aval) == 4 * 8 * 4


# -- collective-census -------------------------------------------------------


def _dcn_mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), ("dcn", "ici")
    )


def _sm_record(body, meta, in_spec=None, x=None):
    from jax.sharding import PartitionSpec as P

    mesh = _dcn_mesh()
    sm = compat.shard_map(
        body, mesh=mesh, in_specs=in_spec or P("dcn"),
        out_specs=P(None), check_vma=False,
    )
    x = jnp.ones((8, 256)) if x is None else x
    return record_of(sm, x, meta=meta)


def test_collective_census_flags_wide_inner_outer_collective():
    def body(x):
        return lax.psum(x, ("dcn", "ici"))

    rec = _sm_record(body, {"dcn_axes": ("dcn",)})
    contract, findings = collective_census(rec)
    assert rules_of(findings) == ["collective-census"]
    assert "deployment width" in findings[0].message
    (entry,) = contract["collectives"]
    assert entry["prim"] == "psum" and sorted(entry["axes"]) == \
        ["dcn", "ici"]


def test_collective_census_flags_f32_dcn_allgather_on_bf16_wire():
    def body(x):
        inner = lax.psum(x, "ici")                  # inner stage: fine
        return jnp.sum(lax.all_gather(inner, "dcn"), axis=0)

    rec = _sm_record(body, {"dcn_axes": ("dcn",), "dcn_wire": "bf16"})
    contract, findings = collective_census(rec)
    assert rules_of(findings) == ["collective-census"]
    assert "float32 payload" in findings[0].message
    assert "float32" in contract["dcn_wire_dtypes"]


def test_collective_census_compressed_wire_and_hier_stages_clean():
    def body(x):
        inner = lax.psum(x, "ici")
        wire = lax.all_gather(inner.astype(jnp.bfloat16), "dcn")
        exact = lax.psum(inner, "dcn")              # f32 rerank psum: ok
        return jnp.sum(wire.astype(jnp.float32), axis=0) + exact

    rec = _sm_record(body, {"dcn_axes": ("dcn",), "dcn_wire": "bf16"})
    contract, findings = collective_census(rec)
    assert findings == []
    assert contract["dcn_wire_dtypes"] == ["bfloat16"]


# -- materialization-model ---------------------------------------------------


def _tile_scan(q, slabs):
    """The legacy grouped-scan shape: a (1, qcap, L) f32 einsum tile
    materialized inside a lax.map body."""
    def blk(mb):
        d2 = jnp.einsum("bqd,bld->bql", q[None], mb[None])
        return jnp.min(d2, axis=2)[0]

    return lax.map(blk, slabs)


def test_materialization_flags_qcap_maxlist_f32_tile_in_scan():
    q = jnp.ones((8, 4))
    slabs = jnp.ones((3, 32, 4))
    rec = record_of(_tile_scan, q, slabs,
                    meta={"qcap": 8, "max_list": 32})
    contract, findings = materialization_model(rec)
    assert rules_of(findings) == ["materialization-model"]
    assert "(1, 8, 32)" in findings[0].message
    assert contract["scan_wide_f32_tiles"] >= 1


def test_materialization_allow_wide_tile_pins_without_finding():
    q = jnp.ones((8, 4))
    slabs = jnp.ones((3, 32, 4))
    rec = record_of(_tile_scan, q, slabs,
                    meta={"qcap": 8, "max_list": 32,
                          "allow_wide_tile": True})
    contract, findings = materialization_model(rec)
    assert findings == []
    assert contract["scan_wide_f32_tiles"] >= 1   # census still pinned
    assert contract["peak_eqn_bytes"] >= 8 * 32 * 4


def test_materialization_negative_outside_scan_and_other_shapes():
    # the same tile OUTSIDE a scan, and non-(qcap, L) shapes inside one
    def flat(q, m):
        return jnp.min(jnp.einsum("bqd,bld->bql", q, m), axis=2)

    rec = record_of(flat, jnp.ones((1, 8, 4)), jnp.ones((1, 32, 4)),
                    meta={"qcap": 8, "max_list": 32})
    _, findings = materialization_model(rec)
    assert findings == []

    def narrow_scan(q, slabs):
        def blk(mb):
            return q @ mb.T                      # (qcap, L) 2-d: clean

        return lax.map(blk, slabs)

    rec2 = record_of(narrow_scan, jnp.ones((8, 4)), jnp.ones((3, 32, 4)),
                     meta={"qcap": 8, "max_list": 32})
    contract2, findings2 = materialization_model(rec2)
    assert findings2 == [] and contract2["scan_wide_f32_tiles"] == 0


# -- dtype-flow --------------------------------------------------------------


def test_dtype_flow_census_and_upcast_budget():
    def f(x):
        y = x.astype(jnp.bfloat16)
        return y.astype(jnp.float32) + x

    rec = record_of(f, jnp.ones((4,)),
                    meta={"max_bf16_to_f32": 0})
    contract, findings = dtype_flow(rec)
    assert contract["casts"]["bfloat16->float32"] == 1
    assert contract["casts"]["float32->bfloat16"] == 1
    assert rules_of(findings) == ["dtype-flow"]
    assert "sanctions at most 0" in findings[0].message

    rec2 = record_of(f, jnp.ones((4,)),
                     meta={"max_bf16_to_f32": 1})
    _, findings2 = dtype_flow(rec2)
    assert findings2 == []
    assert contract["dtypes_64bit"] == []


def test_dtype_flow_flags_64bit():
    # x64 is process-global; build the 64-bit aval via a synthetic
    # record instead of enabling it (the x64 harness owns that process)
    import dataclasses as dc

    from jax.extend.core import ClosedJaxpr

    def f(x):
        return x + 1

    rec = record_of(f, jnp.ones((4,)))
    real = [s for s in walk_jaxpr(rec.jaxpr)][0]
    fake_aval = jax.core.ShapedArray((4,), jnp.dtype("float64"))

    class FakeVar:
        aval = fake_aval

    fake_eqn = real.eqn.replace(outvars=[FakeVar()])
    fake_jaxpr = rec.jaxpr.jaxpr.replace(eqns=[fake_eqn])
    rec64 = dc.replace(rec, jaxpr=ClosedJaxpr(fake_jaxpr, []))
    contract, findings = dtype_flow(rec64)
    assert rules_of(findings) == ["dtype-flow"]
    assert "float64" in findings[0].message
    assert contract["dtypes_64bit"] == ["float64"]


# -- donation-check ----------------------------------------------------------


def test_donation_check_positive_and_negative():
    import functools

    @functools.partial(jax.jit, donate_argnums=(0,))
    def donating(q, w):
        return q * w

    traced = donating.trace(jnp.ones((4,)), jnp.ones((4,)))
    assert donated_leaves(traced) == [0]
    rec = record_from_traced(
        "ok", traced, {"expect_donated_queries": True}
    )
    _, findings = donation_check(rec)
    assert findings == []

    @jax.jit
    def not_donating(q, w):
        return q * w

    traced2 = not_donating.trace(jnp.ones((4,)), jnp.ones((4,)))
    rec2 = record_from_traced(
        "bad", traced2, {"expect_donated_queries": True}
    )
    contract2, findings2 = donation_check(rec2)
    assert rules_of(findings2) == ["donation-check"]
    assert "donates NO input buffer" in findings2[0].message
    assert contract2["donated"] == []


# -- program-count -----------------------------------------------------------


def test_program_count_pass_and_flip_census():
    rec = ProgramRecord("ok", None, program_count=1)
    contract, findings = program_count(rec)
    assert findings == [] and contract["program_count"] == 1

    rec2 = ProgramRecord("bad", None, program_count=3)
    _, findings2 = program_count(rec2)
    assert rules_of(findings2) == ["program-count"]
    assert "zero-retrace" in findings2[0].message

    # the census itself: a prepare whose STATICS leak a runtime value
    # resolves to two distinct programs; a clean prepare to one
    @jax.jit
    def serve(x):
        return x * 2

    @jax.jit
    def serve_retraced(x):
        return x * 3

    q = jnp.ones((4,))

    def prep_clean(alive):
        return serve, (q,), False

    def prep_leaky(alive):
        # the mutation-retrace hazard: a static derived from the mask
        fn = serve if int(np.asarray(alive).sum()) == 8 else serve_retraced
        return fn, (q,), False

    flips = [{"alive": np.ones(8)}, {"alive": np.r_[np.zeros(1),
                                                   np.ones(7)]}]
    assert flip_census(prep_clean, flips) == 1
    assert flip_census(prep_leaky, flips) == 2


# -- contract drift mechanics ------------------------------------------------


def test_check_drift_both_directions_and_field_diffs():
    live = {"a": {"x": 1, "nested": {"y": 2}}, "b": {"x": 1}}
    ok = check_drift(live, {"a": {"x": 1, "nested": {"y": 2}},
                            "b": {"x": 1}})
    assert ok == []
    # changed field
    fs = check_drift(live, {"a": {"x": 1, "nested": {"y": 3}},
                            "b": {"x": 1}})
    assert len(fs) == 1 and "nested.y" in fs[0].message
    assert fs[0].rule == "program-contract"
    # stale snapshot entry (program removed)
    fs2 = check_drift({"a": live["a"]}, {"a": live["a"], "b": {"x": 1}})
    assert len(fs2) == 1 and "no longer exists" in fs2[0].message
    # unpinned live program
    fs3 = check_drift(live, {"a": live["a"]})
    assert len(fs3) == 1 and "no committed contract" in fs3[0].message


def test_run_passes_merges_all_passes_and_meta():
    def f(x):
        return x * 2

    rec = record_of(f, jnp.ones((4,)),
                    meta={"qcap": 8, "note_obj": object()})
    contract, findings = run_passes(rec)
    assert findings == []
    for key in ("meta", "collectives", "peak_eqn_bytes", "casts",
                "donated", "program_count"):
        assert key in contract
    assert contract["meta"] == {"qcap": 8}   # non-JSON meta dropped
    assert [p.name for p in ALL_PASSES] == [
        "collective-census", "materialization-model", "dtype-flow",
        "donation-check", "program-count",
    ]


# -- the full registry (slow tier: toy-world builds) -------------------------


@pytest.fixture(scope="module")
def live_audit():
    from raft_tpu.analysis.program.contracts import audit_programs

    return audit_programs(count=True)


@pytest.mark.slow
def test_registry_covers_entry_points_and_audits_clean(live_audit):
    live, findings = live_audit
    assert findings == [], [f.render() for f in findings]
    assert len(live) >= 8
    # the serving surface is covered: every engine family, the probe,
    # both mnmg variants incl. failover+mutation, and the hier merge
    for name in (
        "ivf_flat_grouped_pallas", "ivf_pq_grouped_pallas",
        "ivf_sq_grouped_pallas", "two_level_probe_kernel",
        "mnmg_pq_fused", "mnmg_pq_fused_failover_mutation",
        "mnmg_flat_fused_failover_mutation", "mnmg_pq_hier_merge",
    ):
        assert name in live, name
    # physics pinned: kernel engines materialize no wide tile, legacy
    # engines do (and say so), serving queries donate, flips retrace
    # nothing, the DCN wire is compressed
    assert live["ivf_flat_grouped_pallas"]["scan_wide_f32_tiles"] == 0
    assert live["ivf_pq_grouped_pallas"]["scan_wide_f32_tiles"] == 0
    assert live["ivf_flat_grouped_xla"]["scan_wide_f32_tiles"] > 0
    assert live["ivf_pq_grouped_onehot"]["scan_wide_f32_tiles"] > 0
    assert live["mnmg_pq_fused"]["donated"] != []
    assert live["mnmg_pq_fused_failover_mutation"]["program_count"] == 1
    assert live["mnmg_flat_fused_failover_mutation"]["program_count"] == 1
    assert live["mnmg_pq_hier_merge"]["dcn_wire_dtypes"] == [
        "bfloat16", "int32",
    ]


@pytest.mark.slow
def test_committed_contracts_pin_live_programs_no_drift(live_audit):
    """The drift-check ratchet (the jaxlint-baseline discipline): every
    committed snapshot entry must match a LIVE program exactly — stale
    entries fail, unpinned live programs fail, changed fields fail."""
    live, _ = live_audit
    committed = load_contracts(CONTRACTS)
    assert len(committed) >= 8
    drift = check_drift(live, committed)
    assert drift == [], [f.render() for f in drift]
    # stale-entry direction actually fails
    import copy

    doctored = copy.deepcopy(committed)
    doctored["ghost_program"] = {"peak_eqn_bytes": 1}
    assert any(
        "no longer exists" in f.message
        for f in check_drift(live, doctored)
    )
    # and a field-level regression (the f32-wire shape) actually fails
    doctored2 = copy.deepcopy(committed)
    doctored2["mnmg_pq_hier_merge"]["dcn_wire_dtypes"] = [
        "float32", "int32",
    ]
    fs = check_drift(live, doctored2)
    assert any("dcn_wire_dtypes" in f.message for f in fs)


@pytest.mark.slow
def test_seeded_regression_f32_dcn_wire_flips_red():
    """ISSUE 12 acceptance: forcing the DCN merge onto the uncompressed
    f32 allgather — a change every bit-identity test is blind to —
    produces a hard collective-census finding against the REAL fused
    program, prepared through the serving entry's own front half."""
    from raft_tpu.analysis.program.registry import _World
    from raft_tpu.comms.mnmg_ivf import _prepare_pq_search
    from raft_tpu.comms.multihost import hier_axes

    w = _World.get()
    comms = w.hier_comms
    h = hier_axes(comms.mesh, comms.axis)
    fn, args, _ = _prepare_pq_search(
        comms, w.mnmg_pq, w.q, 4, n_probes=4, qcap=8, refine_ratio=2.0,
        use_pallas=True, wire="f32",
    )
    rec = record_from_traced(
        "seeded_f32_wire", fn.trace(*args),
        {"dcn_axes": (h[0],), "dcn_wire": "bf16"},
    )
    _, findings = run_passes(rec)
    assert "collective-census" in rules_of(findings)
    assert any("float32 payload" in f.message for f in findings)


@pytest.mark.slow
def test_seeded_regression_undonated_queries_flips_red():
    """ISSUE 12 acceptance: un-donating the serving queries produces a
    hard donation-check finding against the real fused program."""
    from raft_tpu.analysis.program.registry import _World
    from raft_tpu.comms.mnmg_ivf import _prepare_pq_search

    w = _World.get()
    fn, args, _ = _prepare_pq_search(
        w.comms, w.mnmg_pq, w.q, 4, n_probes=4, qcap=8,
        refine_ratio=2.0, use_pallas=True, donate_queries=False,
    )
    rec = record_from_traced(
        "seeded_undonated", fn.trace(*args),
        {"expect_donated_queries": True},
    )
    _, findings = run_passes(rec)
    assert rules_of(findings) == ["donation-check"]


@pytest.mark.slow
def test_cli_json_schema_matches_jaxlint(tmp_path):
    """ISSUE 12 satellite: ``--programs --format json`` emits the SAME
    top-level schema as the lint CLI, so the one consumer script parses
    both tiers — and a doctored contracts file flips the exit code."""
    env = dict(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PATH="/usr/bin:/bin:/usr/local/bin",
    )
    import os

    env = {**os.environ, **env}
    proc = subprocess.run(
        [sys.executable, "-m", "raft_tpu.analysis", "--programs",
         "--format", "json"],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    lint = subprocess.run(
        [sys.executable, "-m", "raft_tpu.analysis", "--format", "json",
         "--no-baseline", "ci/checks/style.py"],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert set(payload) == set(json.loads(lint.stdout))
    assert payload["checked_files"] >= 8
    assert payload["findings"] == []
    assert "collective-census" in payload["rules"]
    # doctored snapshot -> findings + exit 1 (the gate goes red)
    doctored = json.loads(CONTRACTS.read_text())
    doctored["programs"]["mnmg_pq_hier_merge"]["dcn_wire_dtypes"] = [
        "float32", "int32",
    ]
    alt = tmp_path / "contracts.json"
    alt.write_text(json.dumps(doctored))
    proc2 = subprocess.run(
        [sys.executable, "-m", "raft_tpu.analysis", "--programs",
         "--format", "json", "--contracts", str(alt)],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert proc2.returncode == 1
    out2 = json.loads(proc2.stdout)
    assert any(f["rule"] == "program-contract" for f in out2["findings"])


@pytest.mark.slow
def test_warmup_audit_spot_check(live_audit):
    """``warmup(audit=True)`` accepts the healthy single-chip engines
    (both modes) and the registry world's caches keep it cheap."""
    del live_audit  # ordering: reuse the already-built world
    from raft_tpu.analysis.program.registry import _World

    w = _World.get()
    assert w.flat_index.warmup(16, k=4, n_probes=4, use_pallas=True,
                               audit=True) == 8
    assert w.flat_index.warmup(16, k=4, n_probes=4, use_pallas=False,
                               audit=True) == 8


def test_list_programs_cli():
    proc = subprocess.run(
        [sys.executable, "-m", "raft_tpu.analysis", "--list-programs"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0
    from raft_tpu.analysis.program.registry import SPECS

    assert len(SPECS) >= 8
    for s in SPECS:
        assert f"{s.name}:" in proc.stdout
