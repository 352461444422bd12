"""What the program adds to the reduced trace (``program_trace.py``) and the
metric readers that read it."""

import glob
import json
import os
import time

import jax
import pytest

import cells
import program_trace
import tracing

MS = 1_000_000
SCOPES = ("obs.backward", "obs.optimizer", "obs.bucketize", "obs.compress", "obs.collective",
          "obs.decode", "obs.apply")
NEW_SCOPE_METRICS = ("optimizer_ms", "bucketize_ms", "compress_ms", "decode_ms", "apply_ms")

HLO = """HloModule jit_train_step, entry_computation_layout={(f32[8,128]{1,0})->f32[8,128]{1,0}}

%fused_computation (param_0: f32[8,128]) -> f32[8,128] {
  %param_0 = f32[8,128]{1,0} parameter(0)
  ROOT %neg = f32[8,128]{1,0} negate(%param_0), metadata={op_name="jit(train_step)/obs.decode/neg"}
}

ENTRY %main (p: f32[8,128]) -> (f32[8,128], f32[8,128]) {
  %p = f32[8,128]{1,0} parameter(0)
  %gte = f32[8,128]{1,0} bitcast(f32[8,128]{1,0} %p)
  %fusion.1 = f32[8,128]{0,1} fusion(f32[8,128]{1,0} %gte), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/obs.compress/mul"}
  %copy.3 = f32[8,128]{1,0} copy(f32[8,128]{0,1} %fusion.1)
  %copy.4 = f32[8,128]{1,0} copy(f32[8,128]{1,0} %gte)
  %add.5 = f32[8,128]{1,0} add(f32[8,128]{1,0} %copy.4, f32[8,128]{1,0} %copy.3), metadata={op_name="jit(train_step)/obs.apply/add"}
  %convert.6 = f32[8,128]{1,0} convert(f32[8,128]{1,0} %p), metadata={op_name="jit(train_step)/convert_element_type"}
  %copy.7 = f32[8,128]{1,0} copy(f32[8,128]{1,0} %p)
  ROOT %tuple = (f32[8,128]{1,0}, f32[8,128]{1,0}) tuple(%add.5, %copy.7)
}
"""


def test_a_metadataless_copy_inherits_its_producers_scope():
    inherited = program_trace.inherited_scopes(HLO)
    # the operand's producer has a scope: take it
    assert inherited["copy.3"] == "obs.compress"
    # producers without one (a bitcast of a parameter): the nearest user's
    assert inherited["copy.4"] == "obs.apply"
    # op_name metadata outside every scope is program code, not the compiler's
    assert "convert.6" not in inherited
    # nothing to inherit from: a parameter in, the module's tuple out
    assert "copy.7" not in inherited
    # scopes come from the instruction's own computation only
    assert inherited["param_0"] == "obs.decode"


def _trace():
    """One chip, two steps in a 20 ms window. Step i's module runs from
    10i ms to 10i + 8 ms; in step 0 the device idles 1 ms inside the module
    (3-4 ms), and 2 ms between the modules (8-10 ms)."""
    ops = []
    for base in (0, 10 * MS):
        ops += [
            ["fusion.1", base, base + 3 * MS, "obs.backward", "", ""],
            ["copy.2", base + 3 * MS, base + 5 * MS, "", "", "obs.backward"],
            ["fusion.3", base + 5 * MS, base + 6 * MS, "obs.optimizer", "", ""],
            ["copy.4", base + 6 * MS, base + 7 * MS, "", "", ""],
            ["fusion.5", base + 7 * MS, base + 8 * MS, "obs.apply", "", ""],
        ]
    ops[1][1] = 4 * MS  # step 0's planted in-step gap
    modules = [["jit_train_step(1)", 0, 8 * MS], ["jit_train_step(1)", 10 * MS, 18 * MS]]
    return {"window": [0, 20 * MS], "devices": [ops], "modules": [modules],
            "host": [["chipbench.dispatch", 0, MS], ["chipbench.wait", MS, 20 * MS]]}


def test_time_under_each_scope_plus_unscoped_is_all_device_time():
    t = _trace()
    ops = t["devices"][0]
    per_scope = {s: sum(o[2] - o[1] for o in ops if program_trace.scope(o).startswith(s)) for s in SCOPES}
    unscoped = sum(o[2] - o[1] for o in ops if not program_trace.scope(o))
    assert sum(per_scope.values()) + unscoped == sum(o[2] - o[1] for o in ops)
    assert per_scope["obs.backward"] == (3 + 1 + 3 + 2) * MS  # with the copies' inherited share
    steps = 2
    assert tracing.load_reader("unscoped_ms").read(t, None, steps) == unscoped / steps / 1e6 == 1.0
    assert tracing.load_reader("optimizer_ms").read(t, None, steps) == 1.0
    assert tracing.load_reader("apply_ms").read(t, None, steps) == 1.0
    # no op under it: nothing to read
    assert tracing.load_reader("decode_ms").read(t, None, steps) is None
    # a trace without the sixth field reads the own scope alone
    for o in ops:
        del o[5]
    assert tracing.load_reader("unscoped_ms").read(t, None, steps) == 2.5


def test_idle_between_steps_places_boundary_and_in_step_gaps():
    t = _trace()
    # the 2 ms between the modules counts; the 1 ms inside step 0 does not
    assert program_trace.idle_between_steps_ns(t) == 2 * MS
    # and the idle share sees both
    assert tracing.busy_ns(t) == [(20 - 1 - 2 - 2) * MS]
    del t["modules"]
    assert program_trace.idle_between_steps_ns(t) is None


def test_setup_readers_read_the_programs_spans(monkeypatch):
    t = _trace()
    spans = [
        ["obs.setup.init", -9 * MS, -5 * MS, "", ""],
        ["obs.compile", -8 * MS, -7 * MS, "obs.setup.init", "jit(_normal)"],
        ["obs.setup.build", -5 * MS, -4 * MS, "", ""],
        ["obs.setup.place", -4 * MS, -3 * MS, "", ""],
        ["obs.compile", -3 * MS, -1 * MS, "", "jit(train_step)"],
        ["obs.compile", 12 * MS, 13 * MS, "", "jit(train_step)"],  # in the window
    ]
    t["program"] = spans
    assert tracing.load_reader("setup.init_s").read(t, None, 2) == pytest.approx(0.004)
    # outside init, before the window: the step's compile alone
    assert tracing.load_reader("setup.compile_s").read(t, None, 2) == pytest.approx(0.002)
    # a program without a recorder (and a trace without spans): nothing to read
    del t["program"]
    monkeypatch.delattr("repro.obs.trace.RECORDER")
    for name in ("setup.init_s", "setup.compile_s"):
        assert tracing.load_reader(name).read(t, None, 2) is None


def test_setup_readers_fall_back_to_the_live_recorder(monkeypatch):
    from repro.obs import trace as obs_trace

    rec = obs_trace.Recorder()
    monkeypatch.setattr(obs_trace, "RECORDER", rec)
    rec.spans += [obs_trace.Span("obs.setup.init", 0, 3 * MS, ""),
                  obs_trace.Span("obs.compile", MS, 2 * MS, "obs.setup.init", "jit(_normal)"),
                  obs_trace.Span("obs.compile", 4 * MS, 9 * MS, "", "jit(train_step)")]
    t = _trace()
    assert tracing.load_reader("setup.init_s").read(t, None, 2) == pytest.approx(0.003)
    assert tracing.load_reader("setup.compile_s").read(t, None, 2) == pytest.approx(0.005)


def test_anchor_maps_a_recorded_span_onto_a_cpu_profile(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    from repro.obs import trace as obs_trace

    rec = obs_trace.Recorder()
    monkeypatch.setattr(obs_trace, "RECORDER", rec)
    rec.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        wall = rec.anchor()
        time.sleep(0.005)
        with obs_trace.host_span("region"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
        rec.stop()
    data = ProfileData.from_file(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)[0])
    anchor = program_trace.anchor_start(data)
    assert anchor is not None
    mapped = program_trace.on_trace_clock([tuple(s) for s in rec.spans], wall, anchor)
    region = next(s for s in mapped if s[0] == "obs.region")
    seen = [ev for p in data.planes for line in p.lines for ev in line.events if ev.name == "obs.region"]
    assert len(seen) == 1
    assert abs(region[1] - seen[0].start_ns) < 200_000
    assert abs(region[2] - seen[0].end_ns) < 200_000


RECORDED = cells.HERE / "tests" / "data" / "granite_moe.ef.w1.program_trace.json"


def test_recorded_trace_reads_every_new_metric():
    """A reduced trace of two steps of ``granite_moe.ef.w1`` on a v5e, with
    the inherited scopes, module events and the program's spans."""
    t = json.loads(RECORDED.read_text())
    cell = cells.load("granite_moe.ef.w1")
    steps = t["steps"]
    values = {m: tracing.load_reader(m).read(t, cell, steps)
              for m in ("unscoped_ms", *NEW_SCOPE_METRICS, "setup.init_s", "setup.compile_s")}
    assert all(v is not None and v >= 0 for v in values.values()), values
    # the split is exhaustive: every op is under one scope or unscoped
    ops = tracing.in_window(t, t["devices"][0])
    lo, hi = t["window"]
    total = sum(o[2] - o[1] for o in ops)
    per_scope = sum(o[2] - o[1] for o in ops if program_trace.scope(o).startswith(SCOPES))
    assert per_scope + sum(o[2] - o[1] for o in ops if not program_trace.scope(o)) == total
    assert values["unscoped_ms"] < 0.05 * total / steps / 1e6
    between = program_trace.idle_between_steps_ns(t)
    assert between is not None and 0 <= between <= (hi - lo) - tracing.busy_ns(t)[0]
    # the program's spans sit on the trace's clock: set-up before the window
    setup = [s for s in t["program"] if s[0].startswith("obs.setup.")]
    assert [s[0] for s in setup] == ["obs.setup.init", "obs.setup.build", "obs.setup.place"]
    assert all(s[2] <= lo for s in setup)
