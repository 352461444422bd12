"""The reduction from a device trace to per-layer metrics."""

import json

import pytest

import cells
import tracing

MS = 1_000_000


def _trace():
    """Two chips, 10 ms window, two steps: model ops, exchange ops and a
    collective that overlaps an exchange op on chip 0 for 1 ms."""
    d0 = [
        ["fusion.1", 0, 3 * MS, "", ""],
        ["fusion.2", 2 * MS, 4 * MS, "", ""],  # overlaps fusion.1
        ["custom-call.7", 4 * MS, 5 * MS, "obs.compress", "ef_sign_bucket_step->(u32[5672,2048], f32[5672,65536])"],
        ["all-gather.3", 5 * MS, 7 * MS, "obs.collective.xla", ""],
        ["fusion.9", 6 * MS, 7 * MS, "obs.apply", ""],
        ["fusion.1", 8 * MS, 9 * MS, "", ""],
    ]
    d1 = [
        ["fusion.1", 1 * MS, 2 * MS, "", ""],
        ["all-gather.3", 5 * MS, 7 * MS, "obs.collective.xla", ""],
    ]
    host = [
        ["chipbench.dispatch", 0, MS // 2],
        ["chipbench.wait", MS, 10 * MS],
    ]
    return {"window": [0, 10 * MS], "devices": [d0, d1], "host": host, "device_kind": "TPU v5 lite"}


def test_union_subtract_clip():
    assert tracing.union([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]
    assert tracing.length([(0, 4), (6, 10)]) == 8


def test_busy_and_idle_share():
    t = _trace()
    assert tracing.busy_ns(t) == [8 * MS, 3 * MS]
    idle = tracing.load_reader("idle_share").read(t, None, 2)
    assert idle == pytest.approx(100 * (1 - 5.5 / 10))


def test_time_per_scope():
    t = _trace()
    model = tracing.load_reader("model_ms").read(t, None, 2)
    exchange = tracing.load_reader("exchange_ms").read(t, None, 2)
    # chip 0: model ops 3 + 2 + 1 ms, exchange 1 + 2 + 1 ms; chip 1: 1 and 2 ms
    assert model == pytest.approx((6 + 1) / 2 / 2)
    assert exchange == pytest.approx((4 + 2) / 2 / 2)


def test_nothing_to_read_gives_nothing():
    t = _trace()
    for ops in t["devices"]:
        for o in ops:
            o[3] = ""
    assert tracing.load_reader("exchange_ms").read(t, None, 2) is None


def test_kernel_roofline_and_mfu():
    t = _trace()
    cell = cells.load("granite_moe.ef.w1")
    roof = tracing.load_reader("ef_kernels.roofline").read(t, cell, 2)
    import counts

    nb = counts.n_buckets(cell.config, 65536)
    assert roof == pytest.approx(100 * counts.compress_bytes(nb, 65536) / 819e9 / 1e-3)
    mfu = tracing.load_reader("mfu").read(t, cell, 2)
    assert mfu == pytest.approx(100 * 2 * 3.715759079424e12 / 0.010 / 197e12)


def test_breakdown_names_gaps_by_host_span():
    b = tracing.breakdown(_trace())
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert b["idle_gaps"][0] == ["chipbench.wait", pytest.approx(0.001)]


RECORDED = cells.HERE / "tests" / "data" / "granite_moe.ef.w1.trace.json"


def _sweep(trace):
    """The same numbers by a sweep over op boundaries, counting running ops."""
    lo, hi = trace["window"]
    busy, scope = [], {}
    for ops in trace["devices"]:
        edges = []
        for o in ops:
            s, e = max(o[1], lo), min(o[2], hi)
            if s < e:
                edges += [(s, 1), (e, -1)]
                scope[o[3]] = scope.get(o[3], 0) + o[2] - o[1]
        edges.sort()
        b = run = 0
        prev = lo
        for t, d in edges:
            if run:
                b += t - prev
            run += d
            prev = t
        busy.append(b)
    return busy, scope


def test_recorded_trace():
    """A reduced trace of two steps of ``granite_moe.ef.w1`` on a v5e."""
    t = json.loads(RECORDED.read_text())
    busy, scope = _sweep(t)
    assert tracing.busy_ns(t) == busy
    steps = t["steps"]
    chips = len(t["devices"])
    exchange = sum(v for k, v in scope.items() if k.startswith(tracing.EXCHANGE_SCOPES))
    model = sum(v for k, v in scope.items() if not k.startswith(tracing.EXCHANGE_SCOPES))
    assert tracing.load_reader("exchange_ms").read(t, None, steps) == pytest.approx(exchange / chips / steps / 1e6)
    assert tracing.load_reader("model_ms").read(t, None, steps) == pytest.approx(model / chips / steps / 1e6)
    lo, hi = t["window"]
    idle = tracing.load_reader("idle_share").read(t, None, steps)
    assert idle == pytest.approx(100 * (1 - sum(busy) / chips / (hi - lo)))
    assert 0 < idle < 100
    # every EF scope and all three bucket kernels are found
    assert {"obs.backward", "obs.bucketize", "obs.compress", "obs.decode", "obs.apply"} <= set(scope)
    roof = tracing.load_reader("ef_kernels.roofline").read(t, cells.load("granite_moe.ef.w1"), steps)
    assert 0 < roof < 100
