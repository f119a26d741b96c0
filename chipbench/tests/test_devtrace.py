"""Trace reduction: busy and idle union, program split, kernel time,
roofline share and breakdown, on a small trace whose answers are known,
and the reader on a trace JAX's profiler records here."""

import jax
import jax.numpy as jnp
import pytest

import devtrace
import families
import flops
import run
from devtrace import Event, Trace
from loop import Log, StepLog

MS = 1_000_000     # ns

FLASH = ("custom-call.7", (("long_name", "pallas_call _flash_tri_kernel"),))


def _trace():
    """Window [0, 100) ms on device 0: a prefill [0, 30) holding two
    flash calls of 5 ms, a decode [40, 60) holding a 10 ms fusion inside
    a 16 ms while loop, an eager scatter [70, 72), then nothing. Device 1
    is busy [0, 50)."""
    ops0 = [Event(0, 10 * MS, "fusion.1"),
            Event(10 * MS, 5 * MS, FLASH[0], FLASH[1]),
            Event(15 * MS, 5 * MS, FLASH[0], FLASH[1]),
            Event(20 * MS, 10 * MS, "fusion.2"),
            Event(40 * MS, 16 * MS, "while.3"),
            Event(42 * MS, 10 * MS, "fusion.4"),
            Event(56 * MS, 4 * MS, "fusion.5"),
            Event(70 * MS, 2 * MS, "scatter.1"),
            Event(150 * MS, 5 * MS, "fusion.9")]       # after the window
    mods = [Event(0, 30 * MS, "jit__lambda"),
            Event(40 * MS, 20 * MS, "jit__lambda"),
            Event(70 * MS, 2 * MS, "jit_scatter")]
    host = [Event(-5 * MS, 105 * MS, "chipbench.window"),
            Event(0, 30 * MS, "chipbench.step.admit"),
            Event(30 * MS, 40 * MS, "chipbench.step.decode"),
            Event(32 * MS, 6 * MS, "PjitFunction(<lambda>)"),
            Event(75 * MS, 20 * MS, "chipbench.step.decode")]
    host[0] = Event(0, 100 * MS, "chipbench.window")
    return Trace({0: ops0, 1: [Event(0, 50 * MS, "fusion.1")]},
                 {0: mods}, host)


def test_busy_is_the_union_within_the_window_averaged_over_devices():
    t = _trace()
    assert t.window_s() == pytest.approx(0.1)
    # device 0: [0,30) + [40,60) + [70,72) = 52 ms; device 1: 50 ms
    assert t.busy_s() == pytest.approx(0.051)


def test_programs_are_told_apart_by_what_they_hold():
    kinds = [k for k, _ in _trace().programs()]
    assert kinds == ["prefill", "decode", "other"]
    assert len(_trace().flash_ops()) == 2


def test_top_ops_count_self_time():
    top = dict(_trace().top_ops(20))
    assert top["decode:while.3"] == pytest.approx(0.006)   # 16 - 10
    assert top["decode:fusion.4"] == pytest.approx(0.010)
    assert top["prefill:custom-call.7"] == pytest.approx(0.010)
    assert "other:fusion.9" not in top                     # outside


def test_idle_gaps_are_named_by_the_host():
    gaps = _trace().idle_gaps(10)
    assert gaps[0] == ["chipbench.step.decode", pytest.approx(0.028)]
    assert gaps[1] == ["chipbench.step.decode > PjitFunction(<lambda>)",
                       pytest.approx(0.010)]
    assert gaps[2] == ["chipbench.step.decode", pytest.approx(0.010)]
    assert sum(g for _, g in gaps) == pytest.approx(0.1 - 0.052)


def _view(trace, prefills, decode_ctx):
    k = {"d": 64, "ff": 128, "heads": 4, "kv_heads": 4, "head_dim": 16,
         "layers": 2, "vocab": 256}
    lg = Log(steps=[StepLog(0, 1, prefills, decode_ctx)],
             traced_steps=(0, 1))

    class Cell:
        traffic = {"slots": 4}
        family = families.load("dense")
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    return run.RunView(Cell, k, trace, lg, peak, {"compiles_in_window": 0})


def test_readers_on_the_known_trace():
    v = _view(_trace(), [100], [100, 200])
    k, dense = v.k, v.cell.family
    assert run.read_metric("decode_step_ms", v) == pytest.approx(20.0)
    # busy 52 ms on device 0 and 50 ms on device 1
    assert run.read_metric("idle_share", v) == pytest.approx(49.0)
    assert run.read_metric("slot_occupancy", v) == pytest.approx(50.0)
    least = 2 * flops.roofline_s(flops.flash_flops(4, 100, 16),
                                 flops.flash_bytes(4, 100, 16, 2), v.peak)
    assert run.read_metric("flash_roofline", v) == pytest.approx(
        100 * least / 0.010)
    want = (dense.prefill_flops(k, 100) + dense.decode_flops(k, 100)
            + dense.decode_flops(k, 200))
    assert run.read_metric("mfu", v) == pytest.approx(
        100 * want / (0.1 * 1e12))
    assert run.read_metric("prefill_mfu.chat", v) == pytest.approx(
        100 * dense.prefill_flops(k, 100) / (0.030 * 1e12))
    # a variant without a reader of its own is read by its base's
    for name in ("decode_step_ms", "idle_share", "flash_roofline"):
        assert run.read_metric(f"{name}.chat", v) == run.read_metric(name, v)


def test_readers_read_nothing_where_the_counts_disagree():
    v = _view(_trace(), [100, 50], [])          # two prompts, one prefill
    assert run.read_metric("flash_roofline", v) is None
    assert run.read_metric("prefill_mfu.chat", v) is None


def test_a_recorded_trace_gives_the_window(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.step.decode"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = devtrace.load(str(tmp_path))
    names = [e.name for e in t.host]
    assert "chipbench.step.decode" in names
    lo, hi = t.window()
    assert hi > lo
    d = devtrace.describe(str(tmp_path))
    assert any(p["plane"].startswith("/host:") for p in d["planes"])


def test_without_a_flash_kernel_the_most_run_program_is_decode():
    pid = lambda n: (("program_id", n),)
    ops = [Event(i * 10 * MS, 5 * MS, "fusion.1") for i in range(5)]
    mods = [Event(0, 8 * MS, "jit__lambda", pid(7)),
            Event(10 * MS, 8 * MS, "jit__lambda", pid(3)),
            Event(20 * MS, 8 * MS, "jit__lambda", pid(3)),
            Event(30 * MS, 8 * MS, "jit__lambda", pid(3)),
            Event(40 * MS, 8 * MS, "jit_argmax", pid(9))]
    t = Trace({0: ops}, {0: mods}, [Event(0, 50 * MS, "chipbench.window")])
    assert [k for k, _ in t.programs()] == \
        ["prefill", "decode", "decode", "decode", "other"]
