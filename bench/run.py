"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The run needs the chips its cell asks for
and exits non-zero without a result where JAX finds fewer, or another
platform.  It builds its inputs and weights from ``--seed``, warms up
the cell's shapes, measures for about ``--seconds`` and checks what the
measured window produced against the plain reference.  Earlier lines go
to standard error (set-up, compiles inside the window, the memory peak,
and last each number compared beside its limit); the last line of
standard output is the result.  With ``--trace 1`` the window (of at
most ``TRACE_WINDOW_S``) runs under the profiler and the result holds
the cell's per-layer metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time


def _process_start() -> float:
    """``time.perf_counter()`` at the moment this process started."""
    now_pc, now = time.perf_counter(), time.time()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return now_pc - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return now_pc - (now - _IMPORTED)


_IMPORTED = time.time()
T_START = _process_start()


#: A traced run measures a window of at most this many seconds.  The
#: profiler holds a bounded number of device events, and a long window
#: of small operations overflows it: a 13 s window of an xLSTM training
#: step (about half a million operations a step) read a 4 s stretch with
#: no operation where a 3 s window read the device busy throughout.
TRACE_WINDOW_S = 3.0


class Context:
    """What a driver gets from the harness for one run."""

    def __init__(self, args, devices, clock, chips):
        self.seed = args.seed
        self.trace = bool(args.trace)
        self.seconds = (min(args.seconds, TRACE_WINDOW_S) if self.trace
                        else args.seconds)
        self.devices, self.clock, self.chips = devices, clock, chips
        from bench import harness
        self.host = harness.HostClock()
        self.t_start = T_START
        self._trace_dir = None

    def device_info(self) -> dict:
        from bench import harness
        return harness.device_info(self.devices, self.chips)

    def start_trace(self) -> None:
        if self.trace:
            import jax
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_dir)

    def stop_trace(self):
        """The window's ``bench.trace.Trace``, or None when not tracing."""
        if not self.trace:
            return None
        import jax
        from bench import trace
        jax.profiler.stop_trace()
        t0 = time.perf_counter()
        try:
            tr = trace.Trace.from_file(trace.find_xplane(self._trace_dir))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        from bench import harness
        harness.log(phase="trace", read_s=time.perf_counter() - t0,
                    device_ops=tr.n_ops())
        return tr


def parse(argv):
    ap = argparse.ArgumentParser(prog="python3 bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    root = harness.ROOT
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        if not (src / "repro").is_dir():
            raise harness.BenchError(f"no program under {src}")
        cell = harness.Cell(harness.load_benchmark(root), args.workload)
        devices = harness.require_device("tpu", cell.chips)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    harness.enable_compile_cache()
    clock = harness.CompileClock()
    ctx = Context(args, devices, clock, cell.chips)
    try:
        out = cell.driver.run(cell, ctx)
        result = finish(cell, ctx, out)
    finally:
        clock.close()
        ctx.host.close()
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0 if result.get("complete", True) else 3


def finish(cell, ctx, out) -> dict:
    """The result line from a driver's output."""
    from bench import harness
    checks, correct = harness.judge(cell.name, out["readings"])
    metrics, complete = {}, True
    device = dict(out["device"])
    breakdown = None
    if not ctx.trace:
        for m in cell.end_to_end:
            if m["name"] not in out["values"]:
                raise harness.BenchError(
                    f"the driver measured no {m['name']!r}")
            metrics[m["name"]] = {"value": out["values"][m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = out["trace"]
        harness.log(traced_programs=sorted(tr.module_names()))
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        rctx = {"trace": tr, "counters": out["counters"],
                "config": cell.config, "traffic": cell.traffic,
                "peaks": harness.load_peaks(device["kind"]),
                "device": device}
        for m in cell.per_layer:
            value = harness.load_module("metrics", m["name"]).read(rctx)
            if value is None:
                harness.log(error=f"per-layer metric {m['name']!r} found "
                                  "nothing to read in this cell")
                complete = False
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    result = {"correct": correct,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if not complete:
        result["complete"] = False
    result["checks"] = checks
    return result


if __name__ == "__main__":
    # run as a script from the checkout's root, which is where the
    # package ``bench`` is imported from
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # the compile cache lives inside the checkout, at a fixed path; set
    # before JAX is imported, since JAX reads the variable then
    from bench.harness import CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.exit(main())
