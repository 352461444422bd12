"""Property-based contracts for repro.obs telemetry reducers and wire models.

``hypothesis`` is an optional dev dependency (requirements-dev.txt); the whole
module skips cleanly when it is absent so tier-1 collection never fails — the
deterministic coverage in tests/test_obs.py still runs.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
hnp = pytest.importorskip("hypothesis.extra.numpy")

from repro.core import aggregation
from repro.core.compressors import ScaledSignCompressor
from repro.obs import telemetry as obs_telemetry

ERR_ARRAYS = hnp.arrays(
    np.float32,
    st.tuples(st.integers(1, 6), st.integers(1, 64)),
    # no subnormals: XLA flushes denormals to zero
    elements=st.floats(-1e6, 1e6, width=32, allow_nan=False, allow_subnormal=False),
)


def _layout(n_buckets_per_group, bucket_size):
    """The two attributes the wire models read, without a real param tree."""
    return types.SimpleNamespace(
        bucket_size=bucket_size,
        groups=[types.SimpleNamespace(n_buckets=nb) for nb in n_buckets_per_group],
    )


# no deadline: the first example of each new shape compiles the reducer
@hypothesis.settings(deadline=None)
@hypothesis.given(ERR_ARRAYS)
def test_residual_l2_finite_nonnegative_and_exact(err):
    got = float(obs_telemetry.residual_l2(jnp.asarray(err)))
    assert np.isfinite(got) and got >= 0.0
    np.testing.assert_allclose(got, np.linalg.norm(err.astype(np.float64)), rtol=1e-4)


@hypothesis.given(
    st.lists(st.integers(1, 64), min_size=1, max_size=4),
    st.sampled_from([32, 96, 128, 4096]),
    st.integers(1, 16),
)
def test_wire_models_match_closed_forms(nbs, bucket_size, world):
    layout = _layout(nbs, bucket_size)
    comp = ScaledSignCompressor()
    nb = sum(nbs)
    ag = obs_telemetry.modeled_wire_bytes("ef_allgather", layout, world, comp)
    # the sign family reduces to the closed forms in core.aggregation
    assert ag == aggregation.bucketed_sign_allgather_wire_bytes(nb, bucket_size, world)
    assert obs_telemetry.modeled_wire_bytes("ef_ring", layout, world, comp) == ag
    assert ag == (world - 1) * nb * comp.wire_bits(bucket_size) / 8.0
    mv = obs_telemetry.modeled_wire_bytes("majority_vote", layout, world, comp)
    assert mv == (world - 1) * nb * bucket_size / 8.0
    assert obs_telemetry.modeled_wire_bytes("dense", layout, world, comp) == 8.0 * nb * bucket_size
    # W=1 moves zero compressed bytes under every non-dense strategy
    if world == 1:
        assert ag == mv == 0.0


@hypothesis.given(
    st.lists(st.integers(1, 64), min_size=1, max_size=4),
    st.integers(2, 16),
)
def test_alltoall_model_is_sum_of_per_group_ceils(nbs, world):
    comp = ScaledSignCompressor()
    layout = _layout(nbs, 32)
    got = obs_telemetry.modeled_wire_bytes("ef_alltoall", layout, world, comp)
    expect = sum(
        2 * (world - 1) * (-(-nb // world)) * comp.wire_bits(32) for nb in nbs
    ) / 8.0
    assert got == expect
    # per-group ceils can only round UP relative to one ceil over the total
    total_ceil = 2 * (world - 1) * (-(-sum(nbs) // world)) * comp.wire_bits(32) / 8.0
    assert got >= total_ceil


@hypothesis.given(
    st.lists(st.integers(1, 64), min_size=1, max_size=4),
    st.sampled_from([32, 96, 4096]),
    st.integers(1, 10_000),
)
def test_fed_wire_model_matches_closed_form(nbs, bucket_size, cohort):
    layout = _layout(nbs, bucket_size)
    comp = ScaledSignCompressor()
    got = obs_telemetry.modeled_fed_wire_bytes(layout, cohort, comp)
    # the sign family reduces to the closed form in core.aggregation
    assert got == sum(
        aggregation.fed_round_wire_bytes(nb, bucket_size, cohort) for nb in nbs
    )
    # linear in cohort (only sampled clients pay; no n_clients term at all),
    # and a cohort of W-1 pays exactly the per-device receive bill of a
    # W-worker ef_allgather — the fed tier IS that wire format server-side
    assert got == cohort * obs_telemetry.modeled_fed_wire_bytes(layout, 1, comp)
    assert got == obs_telemetry.modeled_wire_bytes(
        "ef_allgather", layout, cohort + 1, comp
    )


@hypothesis.given(
    st.lists(st.floats(0.0, 1e9, width=32, allow_nan=False), min_size=1, max_size=5)
)
def test_to_host_roundtrips_every_field(group_vals):
    n = len(group_vals)
    t = obs_telemetry.Telemetry(
        err_l2=jnp.asarray(group_vals, jnp.float32),
        density=jnp.linspace(0.0, 1.0, n),
        wire_bytes=jnp.float32(sum(group_vals)),
        group_bytes=jnp.asarray(group_vals, jnp.float32),
        filtered_lanes=jnp.zeros((4,), jnp.float32),
    )
    host = obs_telemetry.to_host(t)
    assert set(host) == {
        "err_l2", "group_density", "group_bytes", "filtered_lanes", "telemetry_wire_bytes",
    }
    assert host["err_l2"] == [float(jnp.float32(v)) for v in group_vals]
    assert all(0.0 <= d <= 1.0 for d in host["group_density"])
    assert host["filtered_lanes"] == [0.0] * 4
    assert host["telemetry_wire_bytes"] == float(jnp.float32(sum(group_vals)))
