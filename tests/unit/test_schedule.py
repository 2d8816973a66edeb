"""comm.schedule: golden-jaxpr collective discovery, the dependence-
preserving hoist pass (bit-exact replay), and the cost-model planner."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deeperspeed_tpu  # noqa: F401 - installs the jax.shard_map shim
from deeperspeed_tpu.comm.schedule import (CollectiveSite, ScheduledStepFn,
                                           find_collectives,
                                           hoist_collectives, plan_schedule)
from deeperspeed_tpu.telemetry.wire import plain_wire_bytes


def _dp_mesh():
    return Mesh(np.array(jax.devices()), ("dp",))


# -------------------------------------------------------------- discovery
def test_find_collectives_shard_map_psum():
    mesh = _dp_mesh()

    def body(x):
        return jax.lax.psum(x * 2.0, "dp")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P())
    closed = jax.make_jaxpr(fn)(jnp.ones((8, 4)))
    sites = find_collectives(closed)
    # check_vma=True shard_map traces psum as the psum_invariant primitive
    psums = [s for s in sites if s.kind == "all_reduce"]
    assert len(psums) == 1
    (site,) = psums
    assert site.primitive.startswith("psum")
    assert site.axes == ("dp",)
    assert site.n_elems == 4          # per-shard payload: (8/8, 4)
    assert site.repeats == 1
    assert "shard_map" in site.path
    assert not site.quantized


def test_find_collectives_scan_multiplies_repeats():
    """A collective inside a scan body executes ``length`` times per step;
    the site must report that multiplier (it scales the wire-byte model)."""
    mesh = _dp_mesh()

    def body(x):
        def step(c, _):
            return c + jax.lax.psum(c, "dp"), None

        out, _ = jax.lax.scan(step, x, None, length=5)
        return out

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    sites = find_collectives(jax.make_jaxpr(fn)(jnp.ones((8, 4))))
    psums = [s for s in sites if s.kind == "all_reduce"]
    assert len(psums) == 1
    assert psums[0].repeats == 5
    assert "scan" in psums[0].path


def test_find_collectives_quantized_payload_tagged():
    """int8 payloads (the qgZ / MoE a2a wire format) are tagged by dtype."""
    mesh = _dp_mesh()

    def body(x):
        return jax.lax.all_gather(x, "dp")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)
    sites = find_collectives(
        jax.make_jaxpr(fn)(jnp.ones((8, 4), dtype=jnp.int8)))
    ags = [s for s in sites if s.kind == "all_gather"]
    assert len(ags) == 1
    assert ags[0].dtype == "int8" and ags[0].quantized


def test_find_collectives_implicit_gspmd_sites():
    """sharding_constraint eqns -- where GSPMD materializes tp/sp
    collectives at compile time -- are reported as kind='implicit', and
    suppressed with include_implicit=False."""
    mesh = _dp_mesh()
    sh = NamedSharding(mesh, P("dp"))

    def fn(x):
        y = jax.lax.with_sharding_constraint(x * 3.0, sh)
        return y.sum()

    closed = jax.make_jaxpr(fn)(jnp.ones((8, 4)))
    sites = find_collectives(closed)
    implicit = [s for s in sites if s.kind == "implicit"]
    assert len(implicit) == 1
    assert implicit[0].n_elems == 32
    assert find_collectives(closed, include_implicit=False) == []


# ------------------------------------------------------------------- hoist
def _late_psum_body(x, w):
    a = x * 2.0                 # the psum's only producer
    b = w + 1.0                 # independent compute the psum can overlap
    c = b * b
    d = jnp.sin(c)
    g = jax.lax.psum(a, "dp")   # traced late; dataflow-legal right after a
    return g + d


def test_hoist_moves_collective_to_earliest_issue_point():
    mesh = _dp_mesh()
    fn = jax.shard_map(_late_psum_body, mesh=mesh,
                       in_specs=(P("dp"), P()), out_specs=P())
    closed = jax.make_jaxpr(fn)(jnp.ones((8, 4)), jnp.ones((4,)))
    new_closed, n_hoisted = hoist_collectives(closed)
    assert n_hoisted == 1

    def psum_pos(cj):
        (eqn,) = [e for e in cj.jaxpr.eqns
                  if e.primitive.name == "shard_map"]
        body = eqn.params["jaxpr"]
        names = [e.primitive.name for e in body.eqns]
        return next(i for i, n in enumerate(names) if n.startswith("psum"))

    # traced after the independent add/mul/sin chain; dataflow-legal right
    # after the mul that produces its operand, so it must move earlier
    assert psum_pos(new_closed) < psum_pos(closed)


def test_hoist_noop_on_tiny_jaxpr():
    closed = jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones(3))
    new_closed, n_hoisted = hoist_collectives(closed)
    assert n_hoisted == 0
    assert [e.primitive.name for e in new_closed.jaxpr.eqns] == [
        e.primitive.name for e in closed.jaxpr.eqns]


def test_scheduled_step_fn_bitexact_and_stats():
    """The rewritten program is a pure dataflow reorder: ScheduledStepFn
    must return bit-identical results to the unwrapped jit, expose the
    pass's stats, and still .lower() for telemetry."""
    mesh = _dp_mesh()
    fn = jax.shard_map(_late_psum_body, mesh=mesh,
                       in_specs=(P("dp"), P()), out_specs=P())
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(8, 4), jnp.float32)
    w = jnp.asarray(rs.randn(4), jnp.float32)

    sched = ScheduledStepFn(fn)
    got = sched(x, w)
    want = jax.jit(fn)(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert sched.n_collectives == 1
    assert sched.n_hoisted == 1
    assert any(s.kind == "all_reduce" for s in sched.sites)
    assert sched.lower(x, w) is not None


def test_scheduled_step_fn_pytree_roundtrip():
    """Dict-in / dict-out pytrees survive the flatten -> eval_jaxpr ->
    unflatten replay (the engine's step takes and returns state trees)."""
    def fn(tree):
        return {"out": tree["x"] * tree["w"], "aux": tree["x"].sum()}

    tree = {"x": jnp.arange(6.0).reshape(2, 3), "w": jnp.full((2, 3), 2.0)}
    sched = ScheduledStepFn(fn)
    got = sched(tree)
    want = jax.jit(fn)(tree)
    assert set(got) == {"out", "aux"}
    np.testing.assert_array_equal(np.asarray(got["out"]),
                                  np.asarray(want["out"]))
    np.testing.assert_array_equal(np.asarray(got["aux"]),
                                  np.asarray(want["aux"]))


# ------------------------------------------------------------------ planner
def test_plan_prefers_deferred_when_allowed():
    grad_bytes, gas, n = 64 * 2**20, 4, 8
    plan = plan_schedule(grad_bytes=grad_bytes, gas=gas, n_ranks=n,
                         deferred_allowed=True, device_kind="TPU v5p")
    assert plan.grad_schedule == "deferred"
    assert plan.hoist and not plan.fallback and not plan.qgz
    assert plan.wire_bytes == pytest.approx(
        plain_wire_bytes("all_reduce", grad_bytes, n))
    assert plan.tag.startswith("deferred") and plan.tag.endswith("+hoist")
    # the per-microbatch candidate was scored (and costs gas x the wire)
    per_mb = [c for c in plan.candidates if c[0] == "per_microbatch"]
    assert len(per_mb) == 1
    assert per_mb[0][2] == pytest.approx(plan.wire_bytes * gas)


def test_plan_blocked_regime_is_planned_not_fallback():
    """tp/sp/pp regimes (deferred_allowed=False) get a PLANNED
    per-microbatch + hoist schedule -- fallback stays False and the reason
    names the blocker."""
    grad_bytes, gas, n = 64 * 2**20, 4, 8
    plan = plan_schedule(
        grad_bytes=grad_bytes, gas=gas, n_ranks=n, deferred_allowed=False,
        blockers=("tp/sp/pp > 1",), device_kind="TPU v5p")
    assert plan.grad_schedule == "per_microbatch"
    assert plan.hoist and not plan.fallback
    assert "tp/sp/pp > 1" in plan.reason
    assert plan.tag == "per_microbatch+hoist"
    assert plan.wire_bytes == pytest.approx(
        plain_wire_bytes("all_reduce", grad_bytes, n) * gas)


def test_plan_qgz_keeps_quantized_schedule():
    plan = plan_schedule(grad_bytes=4 * 2**20, gas=2, n_ranks=8,
                         deferred_allowed=False, qgz=True,
                         device_kind="TPU v5p")
    assert plan.qgz and plan.hoist and not plan.fallback
    assert plan.tag == "quantized+hoist"


def test_plan_scores_configured_bucket_size():
    """A user-configured bucket_mb joins the candidate set alongside the
    built-in options, and the chosen bucket is one of the scored ones."""
    plan = plan_schedule(grad_bytes=256 * 2**20, gas=4, n_ranks=8,
                         deferred_allowed=True, bucket_mb=8.0,
                         device_kind="TPU v5p")
    names = [c[0] for c in plan.candidates]
    assert "deferred[bucket_mb=8]" in names
    assert plan.grad_schedule == "deferred"
    assert plan.bucket_mb in (0.0, 4.0, 8.0, 16.0)


def test_plan_describe_mentions_tag_and_wire():
    plan = plan_schedule(grad_bytes=2**20, gas=2, n_ranks=8,
                         deferred_allowed=True, device_kind="TPU v5p")
    text = plan.describe()
    assert plan.tag in text and "MiB/step" in text


def test_collective_site_quantized_property():
    site = CollectiveSite(path=(), index=0, primitive="psum",
                          kind="all_reduce", dtype="uint8", n_elems=4,
                          repeats=1, axes=("dp",))
    assert site.quantized
