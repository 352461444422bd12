"""Collective-backend registry + CommSpec seam: resolution rules, the error
taxonomy, deprecation shims, the analytic DMA-hop model, and the pallas_dma
kernel's interpret-mode oracles.

Multi-worker parity (spec path vs legacy kwargs, and the ``pallas_dma``
trajectory contract) runs in subprocesses — same isolation pattern as
tests/test_distributed.py — so the main pytest session keeps one CPU device.
"""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    CommSpec,
    PayloadStack,
    backends,
    bucketize,
    collective,
    compressed,
    make_aggregator,
    robust,
)
from repro.comm.errors import (
    BackendCapabilityError,
    CommSpecError,
    PathConfigError,
    ToleranceError,
    UnknownBackendError,
    UnknownStrategyError,
    WireFormatError,
)
from repro.configs.base import ByzConfig, OverlapConfig
from repro.core import aggregation
from repro.core.compressors import ScaledSignCompressor, get_compressor
from repro.kernels import dma_ring, ref
from repro.launch.mesh import make_host_mesh, use_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree():
    return {"x": jnp.linspace(-1, 1, 300, dtype=jnp.float32)}


# ---------------------------------------------------------------------------
# registry + resolution
# ---------------------------------------------------------------------------


def test_registry_names_and_choices():
    assert set(backends.BACKENDS) == {"xla", "ring", "pallas_dma"}
    assert backends.BACKEND_CHOICES == ("auto",) + tuple(backends.BACKENDS)
    for name, be in backends.BACKENDS.items():
        assert be.name == name


def test_lookup_unknown_backend_lists_options():
    with pytest.raises(UnknownBackendError, match="options"):
        backends.lookup("nccl")
    with pytest.raises(UnknownBackendError, match="pallas_dma"):
        backends.lookup("nccl")  # the listing itself names every registered backend


def test_auto_resolution_per_strategy():
    mesh = make_host_mesh(data=1, model=1)
    for strategy, expect in [
        ("ef_ring", "ring"),
        ("ef_allgather", "xla"),  # CPU: no pallas_dma promotion
        ("ef_coord_median", "xla"),
        ("ef_alltoall", "xla"),
        ("dense", "xla"),
    ]:
        spec = CommSpec(strategy=strategy, bucket_size=128)
        assert backends.resolve(spec, mesh, ("data",)).name == expect, strategy


def test_pallas_dma_falls_back_to_ring_off_tpu(caplog):
    if jax.default_backend() == "tpu":
        pytest.skip("fallback path only exists off-TPU")
    mesh = make_host_mesh(data=1, model=1)
    spec = CommSpec(strategy="ef_allgather", bucket_size=128, backend="pallas_dma")
    with caplog.at_level("WARNING"):
        be = backends.resolve(spec, mesh, ("data",))
    assert be.name == "ring"
    assert "falling back" in caplog.text and "pallas_dma" in caplog.text


def test_pallas_dma_unavailable_off_cpu_raises(monkeypatch):
    """The ring substitution is a CPU-test convenience; anywhere else an
    unavailable ``pallas_dma`` is an error, not a silent change of transport."""
    if jax.default_backend() == "tpu":
        pytest.skip("pallas_dma is available on a TPU")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    mesh = make_host_mesh(data=1, model=1)
    spec = CommSpec(strategy="ef_allgather", bucket_size=128, backend="pallas_dma")
    with pytest.raises(BackendCapabilityError, match="remote-DMA ring"):
        backends.resolve(spec, mesh, ("data",))


def test_ring_backend_requires_single_axis():
    mesh = make_host_mesh(data=1, model=1)
    spec = CommSpec(strategy="ef_ring", bucket_size=128)
    with pytest.raises(BackendCapabilityError, match="exactly one EF axis"):
        backends.resolve(spec, mesh, ("data", "model"))


def test_robust_strategies_resolve_on_every_backend():
    """PR 10: robust rides the slot-native exchange — explicit ring (and
    pallas_dma, degrading to ring off-TPU) resolve instead of raising the
    retired robust-needs-xla error; auto keeps the conservative xla."""
    mesh = make_host_mesh(data=1, model=1)
    dma_expect = "pallas_dma" if jax.default_backend() == "tpu" else "ring"
    for strategy in robust.ROBUST_STRATEGIES:
        for backend, expect in [("xla", "xla"), ("ring", "ring"), ("pallas_dma", dma_expect)]:
            spec = CommSpec(strategy=strategy, bucket_size=128, backend=backend)
            assert backends.resolve(spec, mesh, ("data",)).name == expect, (strategy, backend)


def test_mean_only_backend_rejects_robust_strategy():
    """supports_slots is the real capability query that replaced the old
    hard-coded robust×backend special case."""

    class MeanOnly(backends.CollectiveBackend):
        name = "mean_only"
        supports_slots = False

    be = MeanOnly()
    mesh = make_host_mesh(data=1, model=1)
    be.check("ef_allgather", ScaledSignCompressor(), ("data",), mesh)
    with pytest.raises(BackendCapabilityError, match="supports_slots=False"):
        be.check("ef_coord_median", ScaledSignCompressor(), ("data",), mesh)


def test_non_exchange_strategies_stay_xla_only():
    mesh = make_host_mesh(data=1, model=1)
    for strategy in ("dense", "majority_vote", "ef_alltoall"):
        spec = CommSpec(strategy=strategy, bucket_size=128, backend="ring")
        with pytest.raises(BackendCapabilityError, match="xla"):
            backends.resolve(spec, mesh, ("data",))


def test_capability_matrix_cells():
    mesh = make_host_mesh(data=1, model=1)
    mat = backends.capability_matrix(mesh)
    assert set(mat) == set(collective.STRATEGIES)
    for row in mat.values():
        assert set(row) == set(backends.BACKENDS)
    for strategy in robust.ROBUST_STRATEGIES + backends.MEAN_STRATEGIES:
        assert mat[strategy]["xla"] == "ok"
        assert mat[strategy]["ring"] == "ok"
        assert mat[strategy]["pallas_dma"].startswith("ok"), mat[strategy]
    for strategy in ("dense", "majority_vote", "ef_alltoall"):
        assert mat[strategy]["xla"] == "ok"
        assert mat[strategy]["ring"].startswith("--")
        assert mat[strategy]["pallas_dma"].startswith("--")
    # a multi-axis EF world shows up as the rings' single-axis rejection
    mat2 = backends.capability_matrix(mesh, ef_axes=("data", "model"))
    assert mat2["ef_allgather"]["ring"].startswith("--")
    assert mat2["ef_allgather"]["xla"] == "ok"


def test_pallas_dma_backend_speaks_sign_only():
    mesh = make_host_mesh(data=1, model=1)
    be = backends.BACKENDS["pallas_dma"]
    assert be.available() == dma_ring.supported()
    with pytest.raises(BackendCapabilityError, match="sign"):
        be.check("ef_allgather", get_compressor("top_k", k=4), ("data",), mesh)


def test_recommend_backend_consults_latency_model():
    assert backends.recommend_backend(64, 4096, 1) == "xla"
    assert backends.recommend_backend(64, 4096, 2) == "pallas_dma"
    assert backends.recommend_backend(64, 4096, 8) == "pallas_dma"
    assert backends.recommend_backend(64, 4096, 16) == "xla"


# ---------------------------------------------------------------------------
# analytic DMA-hop model
# ---------------------------------------------------------------------------


def test_dma_ring_latency_model_accept_boundary():
    # per-hop launch is amortized against the collective's single launch:
    # accept exactly while (W-1) hop launches cost less than one collective
    # launch (the wire-byte terms are identical on both sides)
    for world in range(2, 12):
        assert aggregation.dma_ring_latency_model(64, 4096, world)["accept"], world
    assert not aggregation.dma_ring_latency_model(64, 4096, 12)["accept"]
    m = aggregation.dma_ring_latency_model(64, 4096, 4)
    assert m["steps"] == 3
    assert m["per_hop_bytes"] == aggregation.bucketed_sign_ring_per_step_bytes(64, 4096)
    assert m["dma_total_us"] == pytest.approx(3 * m["per_hop_us"])


def test_dma_ring_latency_model_degenerate_world_1():
    m = aggregation.dma_ring_latency_model(64, 4096, 1)
    assert m["steps"] == 0 and m["dma_total_us"] == 0.0 and m["accept"]


# ---------------------------------------------------------------------------
# CommSpec validation taxonomy
# ---------------------------------------------------------------------------


def test_spec_unknown_strategy():
    with pytest.raises(UnknownStrategyError, match="unknown bucketed strategy"):
        CommSpec(strategy="ef_warp").validate()


def test_spec_unknown_backend():
    with pytest.raises(UnknownBackendError, match="options"):
        CommSpec(strategy="ef_allgather", bucket_size=128, backend="nccl").validate()


def test_spec_alltoall_wire_format():
    spec = CommSpec(strategy="ef_alltoall", compressor="top_k", bucket_size=128)
    with pytest.raises(WireFormatError, match="sign compressors"):
        spec.validate()


def test_spec_overlap_needs_bucketed_ef_path():
    spec = CommSpec(strategy="dense", overlap=OverlapConfig(n_groups=2))
    with pytest.raises(PathConfigError, match="overlap_groups"):
        spec.validate()


def test_spec_byz_needs_bucketed_ef_path():
    spec = CommSpec(strategy="dense", byz=ByzConfig())
    with pytest.raises(PathConfigError, match="bucketed"):
        spec.validate()


def test_spec_tolerance_is_world_dependent():
    spec = CommSpec(strategy="ef_trimmed_mean", bucket_size=128, byz=ByzConfig(f=1))
    spec.validate()  # structural-only: no world, no breakdown check
    with pytest.raises(ToleranceError, match="0 <= byz_f <= 0"):
        spec.validate(world=2)
    spec.validate(world=4)  # 2f < W: fine
    with pytest.raises(ToleranceError, match="robust"):
        CommSpec(strategy="ef_allgather", bucket_size=128, byz=ByzConfig(f=1)).validate(world=8)


def test_spec_rejects_untileable_sign_bucket_size_on_tpu(monkeypatch):
    """On a TPU the sign wire format always runs the Pallas bucket kernels, so
    a bucket size they cannot tile fails validation instead of falling back."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(WireFormatError, match="bucket_size % 4096"):
        CommSpec(strategy="ef_allgather", bucket_size=128).validate()
    CommSpec(strategy="ef_allgather", bucket_size=8192).validate()
    CommSpec(strategy="dense", bucket_size=128).validate()
    CommSpec(strategy="ef_allgather", compressor="top_k", bucket_size=128).validate()


def test_bucket_ops_never_fall_back_to_ref_on_tpu(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    assert ops._use_pallas(None) == (True, False)
    # a shape no other test traces, so the jit cache holds no CPU-path trace
    words = jnp.zeros((3, 5, 4), jnp.uint32)
    scales = jnp.ones((3, 5), jnp.float32)
    with pytest.raises(ValueError, match="bucket_size % 4096"):
        ops.bucket_decompress_mean(words, scales)
    ref_mean = ops.bucket_decompress_mean(words, scales, force="ref")
    assert ref_mean.shape == (5, 128)


def test_spec_validate_chains_and_errors_are_value_errors():
    spec = CommSpec(strategy="ef_allgather", bucket_size=128)
    assert spec.validate() is spec
    for exc in (
        UnknownStrategyError,
        UnknownBackendError,
        BackendCapabilityError,
        ToleranceError,
        WireFormatError,
        PathConfigError,
    ):
        assert issubclass(exc, CommSpecError) and issubclass(exc, ValueError)


# ---------------------------------------------------------------------------
# deprecation shims (the only sanctioned users of the legacy factories —
# pyproject turns these warnings into errors everywhere else)
# ---------------------------------------------------------------------------


def test_legacy_bucketed_factory_warns_and_matches_spec_path():
    mesh = make_host_mesh(data=1, model=1)
    tree = _tree()
    layout = bucketize.build_layout(tree, 128)
    comp = ScaledSignCompressor()
    buckets_w = tuple(b[None] for b in bucketize.flatten_buckets(layout, tree))
    err = tuple(jnp.ones_like(b) * 0.1 for b in buckets_w)
    key = jax.random.PRNGKey(0)
    with use_mesh(mesh):
        with pytest.warns(DeprecationWarning, match="make_bucketed_aggregator"):
            legacy = collective.make_bucketed_aggregator(
                "ef_allgather", comp, layout, mesh, ("data",)
            )
        spec = CommSpec(strategy="ef_allgather", compressor=comp, bucket_size=128)
        spec_path = make_aggregator(spec, layout, mesh, ("data",))
        o1, o2 = legacy(buckets_w, err, (), key), spec_path(buckets_w, err, (), key)
    for a, b in zip(o1[0] + o1[1], o2[0] + o2[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_legacy_overlapped_factory_warns():
    from repro.overlap import build_schedule, make_overlapped_aggregator

    mesh = make_host_mesh(data=1, model=1)
    tree = _tree()
    layout = bucketize.build_layout(tree, 64)
    sched = build_schedule(layout, tree, n_groups=2)
    with pytest.warns(DeprecationWarning, match="make_overlapped_aggregator"):
        make_overlapped_aggregator(
            "ef_allgather", ScaledSignCompressor(), layout, sched, mesh, ("data",)
        )


def test_legacy_factory_keeps_canonical_tolerance_error():
    mesh = make_host_mesh(data=1, model=1)
    layout = bucketize.build_layout(_tree(), 128)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        with pytest.raises(ToleranceError, match="byz_f must be >= 0"):
            collective.make_bucketed_aggregator(
                "ef_coord_median", ScaledSignCompressor(), layout, mesh, ("data",), byz_f=-1
            )


# ---------------------------------------------------------------------------
# deprecated backend surface (PR 10 slot-native shims — pyproject errors
# these warnings repo-wide; pytest.warns overrides the filter here)
# ---------------------------------------------------------------------------


class _CannedBackend(backends.CollectiveBackend):
    """Exchange needing no axis context: the payload already carries (W,)."""

    name = "canned"

    def exchange(self, comp, payload, bucket_size, ef_axes, world):
        return PayloadStack(comp, bucket_size, world, slots=payload)


def _gathered_payload(world: int, nb: int = 2, bs: int = 128):
    comp = ScaledSignCompressor()
    rng = np.random.default_rng(world)
    b_w = jnp.asarray(rng.normal(size=(world, nb, bs)).astype(np.float32))
    payload_w, _, _ = jax.vmap(lambda b, e: compressed.ef_encode_buckets(comp, b, e))(
        b_w, jnp.zeros_like(b_w)
    )
    return comp, compressed.BucketPayload(data=payload_w.data)


def test_supports_stack_shim_warns_and_maps_to_supports_slots():
    with pytest.warns(DeprecationWarning, match="supports_stack is deprecated"):
        assert _CannedBackend().supports_stack is True


def test_decode_mean_shim_warns_and_delegates_to_exchange_mean():
    be = _CannedBackend()
    comp, gathered = _gathered_payload(3)
    with pytest.warns(DeprecationWarning, match=r"decode_mean\(\) is deprecated"):
        got = be.decode_mean(comp, gathered, 128, (), 3)
    want = compressed.decode_mean_buckets(comp, gathered, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gather_stack_shim_warns_and_returns_slots():
    be = _CannedBackend()
    _, gathered = _gathered_payload(2)
    with pytest.warns(DeprecationWarning, match=r"gather_stack\(\) is deprecated"):
        out = be.gather_stack(gathered, ())
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(gathered)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# PayloadStack view semantics
# ---------------------------------------------------------------------------


def test_payload_stack_needs_exactly_one_slot_source():
    comp, gathered = _gathered_payload(2)
    with pytest.raises(ValueError, match="exactly one"):
        PayloadStack(comp, 128, 2)
    with pytest.raises(ValueError, match="exactly one"):
        PayloadStack(comp, 128, 2, slots=gathered, slots_fn=lambda: gathered)


def test_payload_stack_readings_match_canonical_decodes():
    comp, gathered = _gathered_payload(4)
    view = PayloadStack(comp, 128, 4, slots=gathered)
    assert not view.fused_mean
    np.testing.assert_array_equal(
        np.asarray(view.decoded()),
        np.asarray(compressed.decode_buckets_stack(comp, gathered, 128)),
    )
    np.testing.assert_array_equal(
        np.asarray(view.mean()),
        np.asarray(compressed.decode_mean_buckets(comp, gathered, 128)),
    )


def test_payload_stack_memoizes_and_never_traces_the_unread_reading():
    comp, gathered = _gathered_payload(3)
    calls = {"slots": 0, "mean": 0}

    def slots_fn():
        calls["slots"] += 1
        return gathered

    def mean_fn():
        calls["mean"] += 1
        return compressed.decode_mean_buckets(comp, gathered, 128)

    view = PayloadStack(comp, 128, 3, slots_fn=slots_fn, mean_fn=mean_fn)
    assert view.fused_mean
    view.mean()
    view.mean()
    # the mean-only consumer never pulls the slot gather into the program
    assert calls == {"slots": 0, "mean": 1}
    view.decoded()
    view.decoded()
    view.slots()
    assert calls == {"slots": 1, "mean": 1}


def test_robust_combine_view_collapses_to_mean_at_f0():
    comp, gathered = _gathered_payload(4)
    view = PayloadStack(comp, 128, 4, slots=gathered)
    np.testing.assert_array_equal(
        np.asarray(robust.combine_view("ef_coord_median", view, 0)),
        np.asarray(compressed.decode_mean_buckets(comp, gathered, 128)),
    )
    stack = compressed.decode_buckets_stack(comp, gathered, 128)
    np.testing.assert_array_equal(
        np.asarray(robust.combine_view("ef_trimmed_mean", view, 1)),
        np.asarray(robust.combine_stack("ef_trimmed_mean", stack, 1)),
    )


# ---------------------------------------------------------------------------
# pallas_dma kernel oracles (interpret mode — run everywhere)
# ---------------------------------------------------------------------------


def _payload_stack(world: int, nb: int = 3, bs: int = 128):
    rng = np.random.default_rng(world)
    g = jnp.asarray(rng.normal(size=(world, nb, bs)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(world, nb, bs)).astype(np.float32) * 0.1)
    scales = jax.vmap(ref.bucket_l1_ref)(g, e) / bs
    words, _ = jax.vmap(ref.bucket_ef_sign_compress_ref)(g, e, scales)
    return words, scales


@pytest.mark.pallas
@pytest.mark.parametrize("world", [2, 3, 4, 8])
def test_dma_ring_slots_ref_is_worker_invariant(world):
    """The hop/arrival schedule files every origin: each worker's canonical
    slots equal the plain all-gather stack — the layout the kernel must hit."""
    words, scales = _payload_stack(world)
    for widx in range(world):
        slot_w, slot_s = ref.dma_ring_slots_ref(words, scales, widx)
        np.testing.assert_array_equal(np.asarray(slot_w), np.asarray(words))
        np.testing.assert_array_equal(np.asarray(slot_s), np.asarray(scales))


@pytest.mark.pallas
@pytest.mark.parametrize("world", [2, 5])
def test_dma_ring_mean_ref_equals_allgather_decode(world):
    words, scales = _payload_stack(world)
    want = ref.bucket_decompress_mean_ref(words, scales)
    for widx in range(world):
        got = ref.dma_ring_mean_ref(words, scales, widx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.pallas
def test_seed_slots_kernel_interpret_world_1():
    """The world==1 degenerate of the DMA kernel (slot seeding, no RDMA) in
    interpret mode: pins the slot-store layout against the ref oracle."""
    if dma_ring.pltpu is None:
        pytest.skip("pallas TPU primitives unavailable in this jax build")
    words, scales = _payload_stack(1)
    slot_w, slot_s = dma_ring.dma_ring_gather_slots(
        jnp.int32(0), words[0], scales[0], world=1, interpret=True
    )
    ref_w, ref_s = ref.dma_ring_slots_ref(words, scales, 0)
    np.testing.assert_array_equal(np.asarray(slot_w), np.asarray(ref_w))
    np.testing.assert_array_equal(np.asarray(slot_s), np.asarray(ref_s))


@pytest.mark.pallas
def test_dma_ring_slot_stack_interpret_world_1():
    """The backend's slot reading of the DMA kernel (dma_ring_slot_stack) at
    the world==1 degenerate, under a manual mesh so the in-kernel origin-id
    derivation (lax.axis_index) has its axis: matches the slots-ref oracle."""
    if dma_ring.pltpu is None:
        pytest.skip("pallas TPU primitives unavailable in this jax build")
    from jax.sharding import PartitionSpec as P

    from repro.utils.compat import shard_map

    words, scales = _payload_stack(1)
    mesh = make_host_mesh(data=1, model=1)

    def body(w, s):
        slot_w, slot_s = dma_ring.dma_ring_slot_stack(w[0], s[0], ("data",), 1, interpret=True)
        return slot_w[None], slot_s[None]

    out_w, out_s = shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data"))
    )(words, scales)
    ref_w, ref_s = ref.dma_ring_slots_ref(words, scales, 0)
    np.testing.assert_array_equal(np.asarray(out_w[0]), np.asarray(ref_w))
    np.testing.assert_array_equal(np.asarray(out_s[0]), np.asarray(ref_s))


# ---------------------------------------------------------------------------
# multi-worker subprocesses: spec-vs-legacy parity, pallas_dma trajectory
# ---------------------------------------------------------------------------

_PARITY_DRIVER = r"""
import os, json, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import jax, jax.numpy as jnp, numpy as np
from repro.comm import CommSpec, bucketize, collective, compressed, make_aggregator
from repro.core.compressors import ScaledSignCompressor
from repro.launch.mesh import make_host_mesh, use_mesh

W = %(world)d
mesh = make_host_mesh(data=W, model=1)
rng = np.random.default_rng(0)
tree = {"a": jnp.zeros((700,), jnp.float32), "b": jnp.zeros((37, 11), jnp.float32)}
layout = bucketize.build_layout(tree, 128)
buckets = bucketize.flatten_buckets(layout, tree)
buckets_w = tuple(jnp.asarray(rng.normal(size=(W,) + b.shape).astype(np.float32))
                  for b in buckets)
err_w = tuple(jnp.asarray(rng.normal(size=b.shape).astype(np.float32) * 0.1)
              for b in buckets_w)
key = jax.random.PRNGKey(0)
comp = ScaledSignCompressor()
out = {}
with use_mesh(mesh):
    for strategy in collective.STRATEGIES:
        has_err = strategy.startswith("ef_")
        err = err_w if has_err else ()
        srv = (tuple(jnp.stack([s] * W) for s in compressed.init_server_buckets(layout, W))
               if strategy == "ef_alltoall" else ())
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = jax.jit(collective.make_bucketed_aggregator(
                strategy, comp, layout, mesh, ("data",)))
        spec = CommSpec(strategy=strategy, compressor=comp, bucket_size=128)
        via_spec = jax.jit(make_aggregator(spec, layout, mesh, ("data",)))
        o1, o2 = legacy(buckets_w, err, srv, key), via_spec(buckets_w, err, srv, key)
        eq = all(np.array_equal(np.asarray(a), np.asarray(b))
                 for a, b in zip(jax.tree.leaves(o1[:3]), jax.tree.leaves(o2[:3])))
        wire_eq = float(o1[3].wire_bytes_per_device) == float(o2[3].wire_bytes_per_device)
        out[strategy] = {"bitwise": bool(eq), "wire_equal": bool(wire_eq)}
print(json.dumps(out))
"""

_TRAJ_DRIVER = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import jax, jax.numpy as jnp, numpy as np
from repro.comm import CommSpec
from repro.configs import get_config, reduced
from repro.core import optim
from repro.launch.mesh import make_host_mesh, ef_axis_names, use_mesh
from repro.sharding.rules import ShardingRules
from repro.train.state import init_train_state
from repro.train import steps as ST

W = %(world)d
cfg = reduced(get_config("llama3_2_1b"))
mesh = make_host_mesh(data=W, model=2)
key = jax.random.PRNGKey(0)
rules = ShardingRules(cfg, mesh, "tp")
ef_axes = ef_axis_names(mesh, "tp")
chain = optim.sgd(0.02)

def run(strategy, backend):
    spec = CommSpec(strategy=strategy, compressor="scaled_sign",
                    bucket_size=4096, backend=backend)
    with use_mesh(mesh):
        state = init_train_state(cfg, key, chain, strategy, mesh, ef_axes,
                                 bucket_size=4096)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                              cfg.vocab_size)}
        bundle = ST.make_train_step(cfg, mesh, rules, spec=spec, local_chain=chain,
            ef_axes=ef_axes, batch_example=batch, state_example=state)
        state = jax.device_put(state, bundle.in_shardings[0])
        batch = jax.device_put(batch, bundle.in_shardings[1])
        fn = bundle.jit()
        traj = []
        for _ in range(5):
            state, (loss, m) = fn(state, batch)
            traj.append(float(loss))
        return traj, jax.device_get(jax.tree.leaves(state.params))

t_ag, p_ag = run("ef_allgather", "auto")
t_dma, p_dma = run("ef_allgather", "pallas_dma")
t_ring, p_ring = run("ef_ring", "auto")
def same(pa, pb):
    return all(np.array_equal(a, b) for a, b in zip(pa, pb))
print(json.dumps({
    "dma_vs_allgather": bool(t_ag == t_dma and same(p_ag, p_dma)),
    "dma_vs_ring": bool(t_dma == t_ring and same(p_dma, p_ring)),
    "traj": t_dma,
}))
"""


_ROBUST_DRIVER = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(world)d"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import jax, jax.numpy as jnp, numpy as np
from repro.comm import CommSpec, bucketize, make_aggregator, robust
from repro.configs.base import ByzConfig, OverlapConfig
from repro.core.compressors import ScaledSignCompressor
from repro.launch.mesh import make_host_mesh, use_mesh
from repro.obs import telemetry as obs_telemetry

W = %(world)d
F = %(byz_f)d
mesh = make_host_mesh(data=W, model=1)
rng = np.random.default_rng(7)
tree = {"a": jnp.zeros((700,), jnp.float32), "b": jnp.zeros((37, 11), jnp.float32)}
layout = bucketize.build_layout(tree, 128)
buckets = bucketize.flatten_buckets(layout, tree)
grads = [tuple(jnp.asarray(rng.normal(size=(W,) + b.shape).astype(np.float32))
               for b in buckets) for _ in range(5)]
comp = ScaledSignCompressor()
key = jax.random.PRNGKey(0)

def run(strategy, backend, f, telemetry="off", overlap=False):
    spec = CommSpec(strategy=strategy, compressor=comp, bucket_size=128,
                    backend=backend, byz=ByzConfig(f=f) if f else None,
                    telemetry=telemetry,
                    overlap=OverlapConfig(n_groups=2) if overlap else None)
    with use_mesh(mesh):
        agg = jax.jit(make_aggregator(spec, layout, mesh, ("data",),
                                      params=tree if overlap else None))
        err = tuple(jnp.zeros_like(b) for b in grads[0])
        outs = info = None
        for g in grads:  # 5-step trajectory: EF residuals feed forward
            outs, err, _, info = agg(g, err, (), key)
        return ([np.asarray(o) for o in outs], [np.asarray(e) for e in err], info)

def same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a[0] + a[1], b[0] + b[1]))

mean_runs = ({be: {"ef_allgather": run("ef_allgather", be, 0),
                   "ef_ring": run("ef_ring", be, 0)}
              for be in ("xla", "ring", "pallas_dma")} if F == 0 else None)
res = {}
for strategy in robust.ROBUST_STRATEGIES:
    r = {}
    base = run(strategy, "xla", F)
    for backend in ("ring", "pallas_dma"):
        r["parity_" + backend] = bool(same(base, run(strategy, backend, F)))
    if F == 0:
        # declared-honest robust == the plain mean strategy, per backend
        for backend, runs in mean_runs.items():
            mean_s = "ef_ring" if backend == "ring" else "ef_allgather"
            r["mean_collapse_" + backend] = bool(
                same(run(strategy, backend, 0), runs[mean_s]))
    else:
        r["overlap_matches_oneshot"] = bool(
            same(base, run(strategy, "xla", F, overlap=True)))
    wire = float(base[2].wire_bytes_per_device)
    r["wire_matches_model"] = wire == obs_telemetry.modeled_wire_bytes(
        strategy, layout, W, comp)
    r["wire_matches_allgather"] = wire == obs_telemetry.modeled_wire_bytes(
        "ef_allgather", layout, W, comp)
    # telemetry="full" emits per-lane filter weights on every transport
    lanes = {}
    for backend in ("xla", "ring", "pallas_dma"):
        t = run(strategy, backend, F, telemetry="full")[2].telemetry
        lanes[backend] = None if t is None else [
            float(x) for x in np.asarray(t.filtered_lanes)]
    r["lanes_shape_ok"] = all(v is not None and len(v) == W for v in lanes.values())
    r["lanes_agree"] = len({tuple(v) for v in lanes.values()}) == 1
    res[strategy] = r
print(json.dumps(res))
"""


# the remote-DMA ring kernel itself, run by Pallas's TPU interpreter, which
# simulates the DMA engines, semaphores and the barrier across CPU devices
_DMA_INTERPRET_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(world)d"
sys.path.insert(0, "%(repo)s/src")
import jax, jax.numpy as jnp, numpy as np
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P
from repro.kernels import dma_ring, ref

W, NB, M = %(world)d, 5, 128
rng = np.random.default_rng(W)
words = jnp.asarray(rng.integers(0, 2**32, size=(W, NB, M), dtype=np.uint32))
scales = jnp.asarray(rng.random((W, NB), dtype=np.float32))
mesh = Mesh(np.array(jax.devices()[:W]), ("data",))

def body(w, s):
    widx = jax.lax.axis_index("data")
    sw, ss = dma_ring.dma_ring_gather_slots(
        widx, w[0], s[0], axis="data", world=W, interpret=pltpu.InterpretParams()
    )
    return sw[None], ss[None]

out_w, out_s = jax.jit(jax.shard_map(
    body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
    check_vma=False,
))(words, scales)
ok = []
for widx in range(W):
    rw, rs = ref.dma_ring_slots_ref(words, scales, widx)
    ok.append(bool(np.array_equal(np.asarray(out_w[widx]), np.asarray(rw))
                   and np.array_equal(np.asarray(out_s[widx]), np.asarray(rs))))
print(json.dumps({"ok": ok}))
"""


def _run_driver(code_tmpl, **kw):
    code = code_tmpl % {"repo": REPO, **kw}
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("world", [2, 4])
def test_spec_path_bitwise_matches_legacy_kwargs(world):
    out = _run_driver(_PARITY_DRIVER, world=world)
    assert set(out) == set(collective.STRATEGIES)
    for strategy, r in out.items():
        assert r["bitwise"], f"{strategy}: spec path diverged from legacy kwargs"
        assert r["wire_equal"], f"{strategy}: wire accounting diverged"


@pytest.mark.slow
@pytest.mark.pallas
@pytest.mark.parametrize("world", [2, 4])
def test_pallas_dma_trajectory_bitwise(world):
    """backend='pallas_dma' (ring fallback off-TPU, the documented degrade)
    trains bitwise-identically to ef_allgather and ef_ring over 5 steps."""
    out = _run_driver(_TRAJ_DRIVER, world=world)
    assert out["dma_vs_allgather"], f"W={world}: pallas_dma diverged: {out['traj']}"
    assert out["dma_vs_ring"], f"W={world}: ring strategy diverged: {out['traj']}"
    assert out["traj"][-1] < out["traj"][0], out["traj"]


@pytest.mark.slow
@pytest.mark.byz
@pytest.mark.parametrize("world,byz_f", [(2, 0), (4, 0), (4, 1), (8, 1)])
def test_robust_strategies_ride_every_backend(world, byz_f):
    """The PR-10 acceptance contract: every robust strategy's 5-step EF
    trajectory is bitwise-equal across xla / ring / pallas_dma (off-TPU
    degrade), byz_f=0 collapses bitwise to the backend's own mean strategy
    (W=2 is f=0-only — 2f < W), robust-under-overlap matches one-shot, the
    wire bill equals the analytic model (== allgather's), and telemetry's
    filtered-lane weights come out identical on every transport."""
    out = _run_driver(_ROBUST_DRIVER, world=world, byz_f=byz_f)
    assert set(out) == set(robust.ROBUST_STRATEGIES)
    for strategy, r in out.items():
        ctx = (strategy, world, byz_f)
        assert r["parity_ring"], ctx
        assert r["parity_pallas_dma"], ctx
        assert r["wire_matches_model"], ctx
        assert r["wire_matches_allgather"], ctx
        assert r["lanes_shape_ok"] and r["lanes_agree"], (ctx, r)
        if byz_f == 0:
            for backend in ("xla", "ring", "pallas_dma"):
                assert r[f"mean_collapse_{backend}"], (ctx, backend)
        else:
            assert r["overlap_matches_oneshot"], ctx


@pytest.mark.slow
@pytest.mark.pallas
@pytest.mark.parametrize("world", [2, 4])
def test_dma_ring_kernel_interpret_matches_slots_ref(world):
    """Every worker's slots after the W−1 remote-DMA hops equal the all-gather
    stack, as the hop-by-hop oracle says."""
    out = _run_driver(_DMA_INTERPRET_SCRIPT, world=world)
    assert out["ok"] == [True] * world
