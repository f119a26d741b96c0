"""Readings for setting a cell's limits and rates, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        readings --seeds 1 2 3 ... [--control]
    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        sweep --rates 2 3 4 5 --seed <n>

``readings`` runs the cell once per seed, as ``run.py`` would, and
prints per seed the widest served gap against the reference (the lower
reading of the limit) and, with ``--control``, the fp8 control's at the
same positions (the upper reading); a control run's ``correct`` is the
control's, judged by the cell's limit. ``sweep`` offers an open-loop cell's
mix at each rate for one window without the reference check, and prints
the waiting queue at the window's start and end, the TTFT tail and the
tokens per second: the knee is the highest rate whose queue does not
grow. Set-up is paid once; each seed draws its own weights. One JSON line
per run goes to standard output.
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "tpu")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    sub = ap.add_subparsers(dest="mode", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--seeds", type=int, nargs="+", required=True)
    r.add_argument("--control", action="store_true")
    s = sub.add_parser("sweep")
    s.add_argument("--rates", type=float, nargs="+", required=True)
    s.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    run.use_compile_cache()
    cell = spec.load_cell(args.workload)
    device = run.check_device(cell.chips)
    clock = run.CompileClock()
    session = run.Session(cell)
    if args.mode == "readings":
        for seed in args.seeds:
            t0 = time.perf_counter()
            res = run.run_cell(cell, seed, args.seconds, False,
                               session=session, control=args.control,
                               t_start=t0, device=device, clock_=clock)
            res.pop("log")
            checks = res["checks"]
            print(json.dumps({"seed": seed, "correct": res["correct"],
                              "logit_gap": res.get(
                                  "program_gap",
                                  checks["logit_gap"]["value"]),
                              "control_gap": (checks["logit_gap"]["value"]
                                              if args.control else None),
                              "checks": checks,
                              "metrics": res["metrics"],
                              "memory_peak_bytes":
                                  res["device"]["memory_peak_bytes"]}),
                  flush=True)
            gc.collect()
        return 0
    for rate in args.rates:
        c = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=rate))
        session.cell = c
        res = run.run_cell(c, args.seed, args.seconds, False,
                           session=session, t_start=time.perf_counter(),
                           device=device, clock_=clock, check=False,
                           drain_s=0.0)
        lg = res["log"]
        inside = [st for st in lg.steps if lg.w0 <= st.end < lg.w1]
        ttft = lg.ttft_s()
        print(json.dumps({
            "rate": rate, "due": res["attempted"],
            "first_token_by_close": len(ttft),
            "pending_start": inside[0].pending if inside else None,
            "pending_end": inside[-1].pending if inside else None,
            "pending_max": max((st.pending for st in inside), default=None),
            "ttft_p90_ms_of_served": (1e3 * float(np.percentile(ttft, 90))
                                      if ttft else None),
            "itl_p95_ms": 1e3 * float(np.percentile(lg.itl_s(), 95)),
            "step_ms_median": 1e3 * float(np.median(
                [st.end - st.start for st in inside])),
            "active_slots_mean": float(np.mean(
                [len(st.decode_ctx) for st in inside])),
            "tokens_per_s": res["metrics"]["tokens_per_s"]["value"]}),
            flush=True)
        del res, lg
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
