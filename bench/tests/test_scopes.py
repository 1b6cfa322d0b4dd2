"""The step's device time split by named scope, the readers of the
program's spans, and the trace reduction with program spans present."""
import glob
import importlib.util
import threading

import jax
import jax.numpy as jnp
import pytest

from benchlib import harness, scopes, spec, tracing

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/adam/mul"}
}

%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %compare.1 = pred[] compare(%a, %b), direction=LT
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0:T(1024)} get-tuple-element(%p), index=1
  %copy.9 = f32[8]{0:T(1024)S(1)} copy(%get-tuple-element.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p, %copy.9)
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="state[\\'params\\']"}
  %top_k.4 = f32[8]{0} custom-call(%x), custom_call_target="TopK", metadata={op_name="jit(step)/compress/decompress/top_k"}
  %sort.3 = (f32[8]{0}, s32[8]{0}) sort(%top_k.4), dimensions={0}, to_apply=%cmp
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %while.5 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/fwd_bwd/while"}
  ROOT %copy.6 = f32[8]{0} copy(%x)
}
"""


def test_scope_of_takes_the_outermost_scope():
    assert scopes.scope_of("jit(step)/compress/decompress/top_k") == \
        "compress"
    assert scopes.scope_of("jit(step)/jit(main)/adam/sqrt") == "adam"
    assert scopes.scope_of("jit(step)/pow") == scopes.OTHER


def test_scope_map_of_a_hand_made_module():
    names = scopes.scope_map(HLO)
    assert names["top_k.4"] == "compress"          # its own op_name
    assert names["fusion.2"] == "adam"             # its fused body
    assert names["sort.3"] == "compress"           # its operand
    assert names["copy.9"] == "fwd_bwd"            # the loop it is in
    assert names["while.5"] == "fwd_bwd"
    assert names["copy.6"] == scopes.OTHER         # nothing to go by


def test_scope_seconds_groups_ops_and_puts_the_rest_under_other():
    op_s = {"%fusion.2 = f32[8]{0} fusion(%x), kind=kLoop": 2.0,
            "top_k.4": 1.0, "sort.3": 0.5, "copy.6": 0.25,
            "copy.9": 0.0625,
            "fusion.77": 0.125}                    # another program's op
    s = scopes.scope_seconds(op_s, scopes.scope_map(HLO))
    assert s == {"fwd_bwd": 0.0625, "compress": 1.5, "decompress": 0.0,
                 "adam": 2.0, "other": 0.375}
    assert sum(s.values()) == sum(op_s.values())
    # a loop's own event spans its body's ops: counting it would count
    # them twice
    with_loop = dict(op_s, **{"while.5": 4.0})
    assert scopes.scope_seconds(with_loop, scopes.scope_map(HLO)) == s


@pytest.mark.parametrize("name,want,absent", [
    ("gpt2l8-lowdiff-diffs", {"fwd_bwd", "compress", "adam"}, set()),
    ("gpt2l8-nockpt", {"fwd_bwd", "adam"}, {"compress", "decompress"})])
def test_the_compiled_step_carries_the_programs_scopes(tiny_cell, name,
                                                       want, absent):
    run = harness.Run(tiny_cell(name), 7, 1.0, True)
    found = set(scopes.scope_map(scopes.step_text(run)).values())
    assert want <= found and not absent & found


def _reader(name):
    path = spec.BENCH / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, mode, spans, steps=0):
        self.mode, self.spans, self.steps = mode, spans, steps
        self.trace_summary = {"op_s": {}}


def _span(name, t0, t1, parent=None):
    return (name, "recovery", 1, "MainThread", t0, t1, None, parent)


def test_span_readers():
    resume = _Run("resume", [
        _span("recovery.h2d_state", 0.0, 1.0), _span("recovery.replay", 1, 3),
        _span("replay.h2d", 1.0, 1.5, "recovery.replay"),
        _span("recovery.h2d_ef", 3.0, 3.25),
        _span("recovery.h2d_state", 4.0, 5.5),
        _span("replay.h2d", 5.5, 5.75, "recovery.replay"),
        _span("recovery.h2d_ef", 6.0, 6.25)])
    assert _reader("resume.h2d_state_s")(resume) == pytest.approx(1.5)
    assert _reader("resume.h2d_payload_s")(resume) == pytest.approx(0.375)
    train = _Run("train", [_span("engine.dispatch", 0.0, 0.002),
                           _span("engine.queue_put", 0.002, 0.003),
                           _span("engine.dispatch", 1.0, 1.004)], steps=2)
    assert _reader("engine.dispatch_ms")(train) == pytest.approx(3.0)


@pytest.mark.parametrize("name", [
    "resume.h2d_state_s", "resume.h2d_payload_s", "engine.dispatch_ms",
    "train_step.fwd_bwd_ms", "train_step.compress_ms",
    "train_step.decompress_ms", "train_step.adam_ms"])
def test_readers_find_nothing_without_the_programs_spans(name):
    """A program without these spans or a device trace (the CPU, or a
    program before them) gives no value and raises nothing."""
    for mode in ("train", "resume"):
        assert _reader(name)(_Run(mode, [], steps=3)) is None


def test_program_spans_leave_the_reduction_as_it_was(tmp_path):
    """The program's spans reach the profiler's trace, and the
    reduction's events, hence every number it gives, are those of the
    benchmark's own spans and the device's ops alone."""
    from repro.obs.trace import TRACER, trace_span
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()

    def worker():
        with trace_span("persist.batch", "persist"):
            f(x).block_until_ready()

    TRACER.clear()
    TRACER.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.recover"):
                with trace_span("recovery.h2d_state", "recovery"):
                    f(x).block_until_ready()
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
    finally:
        jax.profiler.stop_trace()
        TRACER.disable()
        TRACER.clear()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    from jax.profiler import ProfileData
    raw = {ev.name for p in ProfileData.from_file(path).planes
           for line in p.lines for ev in line.events}
    assert {"recovery.h2d_state", "persist.batch"} <= raw
    kept = {e[2] for e in tracing.load_events(path)}
    assert kept == {tracing.WINDOW_SPAN, "bench.recover"}
