"""The readers of the scheduler's spans, on small traces whose answers
are known, and on a trace without the spans (a program that has none)
where each reads nothing."""

import pytest

import run
from devtrace import Event, Trace
from loop import Log, StepLog

MS = 1_000_000     # ns

FLASH = (("long_name", "pallas_call _flash_tri_kernel"),)


def _trace(spans=True):
    """Window [0, 100) ms. Device 0: a prefill [0, 30) holding a flash
    call, a decode [40, 60), an eager scatter [70, 72). Device 1 is busy
    [0, 50). The host admits one request over [0, 30) and one over
    [62, 75), and one before the window."""
    ops0 = [Event(0, 10 * MS, "fusion.1"),
            Event(10 * MS, 10 * MS, "custom-call.7", FLASH),
            Event(20 * MS, 10 * MS, "fusion.2"),
            Event(40 * MS, 20 * MS, "fusion.4"),
            Event(70 * MS, 2 * MS, "scatter.1")]
    mods = [Event(0, 30 * MS, "jit__lambda"),
            Event(40 * MS, 20 * MS, "jit__lambda"),
            Event(70 * MS, 2 * MS, "jit_scatter")]
    host = [Event(-30 * MS, 25 * MS, "chipbench.step.admit"),
            Event(0, 100 * MS, "chipbench.window")]
    if spans:
        admit = lambda t, d, rid, wait: Event(
            t * MS, d * MS, "serve.admit",
            (("rid", rid), ("prompt_len", 64), ("queue_wait_ms", wait)))
        host += [admit(-20, 10, 9, 100.0),
                 Event(0, 60 * MS, "serve.step", (("step", 3),)),
                 admit(0, 30, 0, 5.0),
                 Event(40 * MS, 20 * MS, "serve.decode", (("active", 1),)),
                 Event(62 * MS, 30 * MS, "serve.step", (("step", 4),)),
                 admit(62, 13, 1, 15.0)]
    return Trace({0: ops0, 1: [Event(0, 50 * MS, "fusion.1")]},
                 {0: mods}, sorted(host, key=lambda e: e.start))


def _view(trace):
    k = {"d": 64, "ff": 128, "heads": 4, "kv_heads": 4, "head_dim": 16,
         "layers": 1, "vocab": 256}
    lg = Log(steps=[StepLog(0, 1, [100], [100])], traced_steps=(0, 1))

    class Cell:
        traffic = {"slots": 4}
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    return run.RunView(Cell, k, trace, lg, peak, {"compiles_in_window": 0})


def test_admission_readers_take_the_spans_that_start_in_the_window():
    v = _view(_trace())
    # admissions of 30 and 13 ms; the one at -20 ms is outside
    assert run.read_metric("admit_stall_ms", v) == pytest.approx(21.5)
    assert run.read_metric("admit_stall_ms.chat", v) == pytest.approx(21.5)
    # p90 of 5 and 15 ms, as numpy interpolates it
    assert run.read_metric("queue_wait_p90_ms.chat", v) == pytest.approx(
        14.0)


def test_idle_admit_share_is_idle_time_inside_admissions():
    # device 0 idles 11 of the second admission's 13 ms (the scatter
    # runs [70, 72)), device 1 all 13; the first admission is busy on
    # both: (11 + 13) / 2 of 100 ms
    v = _view(_trace())
    assert run.read_metric("idle_admit_share", v) == pytest.approx(12.0)
    assert run.read_metric("idle_admit_share.chat", v) == pytest.approx(
        12.0)


def test_idle_admit_share_is_zero_without_admissions_in_the_window():
    t = _trace()
    t.host = [e for e in t.host if e.name != "serve.admit"]
    assert run.read_metric("idle_admit_share", _view(t)) == 0.0
    assert run.read_metric("admit_stall_ms", _view(t)) is None


@pytest.mark.parametrize("name", [
    "admit_stall_ms", "admit_stall_ms.chat", "queue_wait_p90_ms.chat",
    "idle_admit_share", "idle_admit_share.chat"])
def test_a_program_without_spans_reads_nothing(name):
    v = _view(_trace(spans=False))
    assert run.read_metric(name, v) is None
    # the readers that were there before still read it
    assert run.read_metric("decode_step_ms", v) == pytest.approx(20.0)
