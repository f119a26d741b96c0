"""On-chip benchmark of the continuous-batching serving path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process on the chip(s) the cell asks for. It reads the cell from
``BENCHMARK.json`` and the files that name finds (``spec.py``), makes the
weights on the device from the seed, loads the ADSALA tuner from an
artifact (installed with the analytic ``SimulatedBackend`` on a
checkout's first run, as ``repro.launch.serve --artifact`` loads one),
builds ``repro.serve.scheduler.ContinuousBatchingScheduler`` and sends
one request per prompt length through it, so every program the window
runs is compiled before it opens. Set-up (``setup_s``) is everything
from the start of this script to the window.

The window offers the cell's traffic (``traffic.py``) for ``--seconds``.
With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under JAX's profiler and the
metrics are the cell's per-layer metrics, each read by
``metrics/<name>.py``. Then the program's state is freed and a sample of
the served requests is compared with the plain reference (``checks.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``, each number compared beside its
limit; the same numbers are the last lines of standard error. Without
an accelerator, or with fewer chips than the cell asks for, it exits 2
and prints no result.

``--keep-trace DIR`` keeps the raw trace of a traced run and a summary
of its planes, lines and busiest events (``devtrace.describe``), for
reading a trace by hand. The fp8 control and the readings that set the
limits are ``calibrate.py``'s.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402

if __name__ == "__main__":
    # a TPU that fails to start must raise, not fall back to the CPU
    os.environ.setdefault("JAX_PLATFORMS", "tpu")

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.join(os.path.dirname(_HERE), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import checks  # noqa: E402
import devtrace  # noqa: E402
import flops  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402
from loop import Server  # noqa: E402

TRACE_DIR = spec.BENCH_DIR / ".cache" / "trace"
TUNER_DIR = spec.BENCH_DIR / ".cache" / "tuner"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Programs built in this process, from JAX's own monitoring events:
    every build records a backend-compile duration, also one that the
    persistent cache serves, which records a cache hit besides."""

    def __init__(self) -> None:
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.compiles


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside this checkout,
    keeping every program however fast it compiled; the program's own
    ``enable_compile_cache`` then takes the directory from the
    environment."""
    from repro.launch.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.JAX_CACHE)
    jax.config.update("jax_compilation_cache_dir", str(spec.JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def check_device(chips: int) -> dict:
    """The accelerator the run measures; exits 2 without one."""
    devs = jax.devices()
    d = devs[0]
    if d.platform not in ("tpu", "gpu"):
        log(f"no accelerator: JAX found {d.platform}")
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"{chips} chips asked for, {len(devs)} found")
        raise SystemExit(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def load_tuner():
    """The ADSALA tuner, from an artifact installed once per checkout
    with the analytic backend (as the bring-up smoke installs it)."""
    from repro.core import AdsalaTuner

    art = TUNER_DIR / "artifact"
    if not (art / "config.json").exists():
        from repro.core import InstallConfig, SimulatedBackend, install

        tmp = TUNER_DIR / "installing"
        shutil.rmtree(tmp, ignore_errors=True)
        cfg = InstallConfig(
            n_samples=48, repeats=2, tile_ids=(0, 3),
            models=("linear_regression", "decision_tree", "xgboost"),
            routines=("gemm", "syrk", "trsm", "attn"),
            grid_budget="small", cv_splits=3, seed=0)
        install(SimulatedBackend(seed=0), cfg, artifact_dir=str(tmp))
        shutil.rmtree(art, ignore_errors=True)
        os.replace(tmp, art)
    return AdsalaTuner.from_artifact(str(art))


class Session:
    """What runs of one cell in one process share: the model, the tuner
    and (through the persistent cache) the compiled programs."""

    def __init__(self, cell: spec.Cell, tuner=True) -> None:
        from repro.configs import build_model

        self.cell = cell
        self.k = cell.k
        self.arch = cell.family.program_config(cell.config)
        self.model = build_model(self.arch)
        self.dtype = jnp.dtype(cell.config["dtype"])
        self.kv_dtype = jnp.dtype(cell.config["kv_dtype"])
        self.tuner = load_tuner() if tuner else None

    def params(self, seed: int):
        return self.cell.family.program_params(self.model, self.k, seed,
                                               self.dtype)

    def scheduler(self, params):
        from repro.serve.scheduler import ContinuousBatchingScheduler

        t = self.cell.traffic
        return ContinuousBatchingScheduler(
            self.model, self.arch, params, slots=t["slots"],
            n_pages=t["pool_pages"], page_size=t["page_size"],
            max_seq_len=t["max_seq_len"], tuner=self.tuner,
            dtype=self.kv_dtype)

    def warm_reqs(self, seed: int, seconds: float) -> list:
        """One request per prompt length the run can send, two output
        tokens each: every prefill, the page seeding of each length and
        the decode step, and no other shape."""
        rng = traffic.seed_rng(seed, 5)
        lengths = traffic.prompt_lengths(self.cell.traffic, seconds)
        return [traffic.Req((-1, i), n, 2, None,
                            rng.integers(0, self.k["vocab"], n,
                                         dtype=np.int32))
                for i, n in enumerate(lengths)]

    def stream(self, seed: int, seconds: float):
        t = self.cell.traffic
        if t["loop"] == "open":
            return traffic.open_loop(t, seed, seconds, self.k["vocab"])
        return traffic.offline_queue(t, seed, self.k["vocab"])


def _pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(cell, log_, seconds: float, setup_s: float) -> dict:
    """The cell's end-to-end metrics, from the host clock."""
    have = {"tokens_per_s": lambda: log_.tokens_in_window() / seconds,
            "ttft_p90_ms": lambda: 1e3 * _pct(log_.ttft_s(), 90),
            "itl_p95_ms": lambda: 1e3 * _pct(log_.itl_s(), 95),
            "setup_s": lambda: setup_s}
    return {m["name"]: {"value": have[m["name"]](), "unit": m["unit"]}
            for m in cell.end_to_end}


class RunView:
    """What a per-layer metric's reader may read."""

    def __init__(self, cell, k, trace, log_, peak, counters) -> None:
        self.cell, self.k, self.trace, self.log = cell, k, trace, log_
        self.peak, self.counters = peak, counters
        self.slots = cell.traffic["slots"]

    def traced_steps(self) -> list:
        i0, i1 = self.log.traced_steps
        return self.log.steps[i0:i1]

    def prefill_lengths(self) -> list[int]:
        return [n for s in self.traced_steps() for n in s.prefills]

    def prefill_flops(self) -> float:
        """Operations of the prompts admitted in the traced steps."""
        fam = self.cell.family
        return sum(fam.prefill_flops(self.k, n)
                   for n in self.prefill_lengths())

    def window_flops(self) -> float:
        """Operations of the traced steps: each admitted prompt and each
        decoded token at its live context, counted by the family."""
        fam, k = self.cell.family, self.k
        return sum(sum(fam.prefill_flops(k, n) for n in s.prefills)
                   + sum(fam.decode_flops(k, c) for c in s.decode_ctx)
                   for s in self.traced_steps())


def read_metric(name: str, view: RunView):
    """``metrics/<name>.py``'s reading; a variant ``<base>.<cells>`` that
    has no reader of its own (one quantity reported under another
    ``moves``) is read by ``metrics/<base>.py``."""
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    if not path.exists():
        path = spec.BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(view)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             session: Session | None = None, control: bool = False,
             t_start: float | None = None, device: dict | None = None,
             keep_trace: str | None = None, clock_: CompileClock | None = None,
             check: bool = True, drain_s: float | None = None) -> dict:
    """One run: set-up, window, metrics, reference check. Returns the
    result object (printing is the caller's). With ``control`` the fp8
    reference's first choices stand in for the served tokens, so the
    control is judged by the same comparison and limit."""
    t_start = T_START if t_start is None else t_start
    clock_ = clock_ or CompileClock()
    session = session or Session(cell)
    k, t = session.k, cell.traffic
    params = jax.block_until_ready(session.params(seed))
    log(f"set-up: weights drawn at {time.perf_counter() - t_start:.3f} s")
    sched = session.scheduler(params)
    hooks_state = {}

    def hooks(event: str) -> None:
        if event == "start":
            hooks_state["compiles"] = clock_.count()
        else:
            hooks_state["compiles"] = clock_.count() - hooks_state["compiles"]
            if trace:
                jax.profiler.stop_trace()

    server = Server(sched, hooks)
    log(f"set-up: scheduler built at {time.perf_counter() - t_start:.3f} s")
    server.warm_up(session.warm_reqs(seed, seconds))
    log(f"set-up: warm-up served at {time.perf_counter() - t_start:.3f} s "
        f"({clock_.compiles} programs built, {clock_.cache_hits} of them "
        f"from the persistent cache)")
    stream = session.stream(seed, seconds)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    if t["loop"] == "open":
        lg = server.run_open(stream, seconds,
                             t["drain_s"] if drain_s is None else drain_s)
    else:
        lg = server.run_offline(stream, seconds, t["queue_depth"])
    setup_s = lg.w0 - t_start

    stats = jax.devices()[0].memory_stats() or {}
    dev = dict(device or {"platform": jax.devices()[0].platform,
                          "kind": jax.devices()[0].device_kind,
                          "count": cell.chips})
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    window = lg.window_reqs()
    if t["loop"] == "open":
        late = np.asarray(lg.lateness)
        log(f"generator lateness: {len(late)} submits, median "
            f"{1e3 * np.median(late):.6f} ms, p99 "
            f"{1e3 * np.percentile(late, 99):.6f} ms, max "
            f"{1e3 * late.max():.6f} ms")
        failed = sum(not lg.reqs[r].done for r in window)
    else:
        failed = 0
    short = sum(1 for r in window if lg.reqs[r].done
                and len(lg.reqs[r].tokens) != lg.reqs[r].req.out_len)
    log(f"window {seconds} s: {len(window)} requests, "
        f"{lg.tokens_in_window()} tokens, {len(lg.steps)} steps in all; "
        f"programs built in window {hooks_state['compiles']}; "
        f"set-up {setup_s:.6f} s, of which building programs "
        f"{clock_.seconds:.6f} s over {clock_.compiles} programs "
        f"({clock_.cache_hits} from the persistent cache)")

    result = {"correct": False, "attempted": len(window), "failed": failed}
    if trace:
        tr = devtrace.load(str(TRACE_DIR))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(devtrace.find(str(TRACE_DIR)),
                        os.path.join(keep_trace, f"{cell.name}.xplane.pb"))
            with open(os.path.join(keep_trace, f"{cell.name}.describe.json"),
                      "w") as f:
                json.dump(devtrace.describe(str(TRACE_DIR)), f)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        view = RunView(cell, k, tr, lg, _peaks(dev),
                       {"compiles_in_window": hooks_state["compiles"]})
        kinds = [kind for kind, _ in tr.programs()]
        log(f"trace: {sum(len(v) for v in tr.ops.values())} device ops, "
            f"window {tr.window_s():.6f} s, busy {tr.busy_s():.6f} s; "
            f"runs prefill {kinds.count('prefill')} decode "
            f"{kinds.count('decode')} other {kinds.count('other')}; "
            f"flash calls {len(tr.flash_ops())}; admitted prompts "
            f"{len(view.prefill_lengths())}")
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], view)
            if v is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = tr.busy_s()
        dev["window_s"] = tr.window_s()
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    else:
        result["metrics"] = end_to_end(cell, lg, seconds, setup_s)
    result["device"] = dev

    result["log"] = lg
    if not check:
        return result
    # the program's state leaves the device before the reference runs
    picked = checks.sample(lg, window, seed, t["check"]["min_tokens"],
                           t["check"]["max_requests"])
    del server, sched, params
    gc.collect()
    t_ref = time.perf_counter()
    cmp = checks.compare(cell, seed, lg, picked, control=control,
                         length=t["max_seq_len"])
    log(f"reference: {cmp['requests']} requests, {cmp['tokens']} served "
        f"tokens compared in {time.perf_counter() - t_ref:.6f} s")
    gap = cmp["logit_gap"]
    if control:
        # the fp8 reference in the program's place: its tokens are judged
        log(f"control (fp8 reference) widest gap {cmp['control_gap']!r}; "
            f"the program's at the same positions {gap!r}")
        result["program_gap"] = gap
        gap = cmp["control_gap"]
    limit = cell.limits["logit_gap"]
    result["checks"] = {
        "logit_gap": {"value": gap, "limit": limit},
        "unfinished": {"value": failed, "limit": 0},
        "wrong_length": {"value": short, "limit": 0},
    }
    result["compared_tokens"] = cmp["tokens"]
    result["correct"] = bool(
        gap <= limit and failed == 0 and short == 0
        and cmp["tokens"] >= 1 and len(window) > 0)
    return result


def _peaks(dev: dict) -> dict:
    return flops.load_peaks(dev["kind"])


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result.pop("log", None)
    checks_ = result.pop("checks")
    result["checks"] = checks_          # the key that comes last
    print(json.dumps(result), flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace and a description of it here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    use_compile_cache()
    cell = spec.load_cell(args.workload)
    device = check_device(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device=device, keep_trace=args.keep_trace)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
