"""Run one cell of the on-chip benchmark.

    python3 perfbench/run.py --workload mistral-7b.batch --seed 7 \
        --seconds 51 --trace 0

One process holds the chip.  It draws the configuration's weights on the
device from ``--seed``, builds the server through the repository's own
construction path, warms exactly the shapes the cell's traffic uses, and
starts the server on a local port.  A load generator in a second process
(``loadgen.py``, no JAX) then drives the cell's traffic mix over HTTP and
stamps every token as it arrives.  After the window the server is
stopped, device memory is read, the program is freed, and a sample of
what it served is compared with the plain reference (``correct.py``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.  A traced run has the profiler on for the last
``TRACE_S`` seconds of the window (at most half of it); the readers that
time on the host clock take the part before, which the profiler would
slow (``Run.host_window``).  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
compared number beside its limit); the last lines of standard error
repeat the checks.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 3.  ``--rehearse`` runs the same path on whatever JAX
finds, with the tiny configuration of the cell's architecture
(``configs/tiny*.json``) and the tiny mix of its traffic kind
(``traffic/rehearsal-<kind>.json``), and marks its line
``"rehearsal": true``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import correct, serve, spec  # noqa: E402
from perfbench.record import Run  # noqa: E402

TRACE_S = 15.0         # profiler window, at the end of the timed one
SAMPLE_REQUESTS = 16   # requests the reference checks, the longest among
LOADGEN_GRACE_S = 300  # past the window before the load is cut
NO_CHIP = 3


class CompileCounter:
    """Backend compiles, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, however
    quick to compile: the served path is hundreds of small ones."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Tracer:
    """The profiler over ``seconds`` from a given time, in a thread of its
    own; ``on`` is when it was started."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.thread = None
        self.on = None

    def start_at(self, t: float) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False

        def profile():
            time.sleep(max(0.0, t - time.monotonic()))
            self.on = time.monotonic()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            time.sleep(self.seconds)
            jax.profiler.stop_trace()

        self.thread = threading.Thread(target=profile, daemon=True)
        self.thread.start()

    def reduce(self, keep_dir=None) -> dict:
        from perfbench import trace_reduce
        try:
            self.thread.join()
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            ev = trace_reduce.extract(files[0])
            if keep_dir:
                os.makedirs(keep_dir, exist_ok=True)
                with gzip.open(os.path.join(keep_dir, "events.json.gz"),
                               "wt") as f:
                    json.dump(ev, f)
            return trace_reduce.reduce(ev)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def drive(server, cell, seed, seconds, tracer, compiles):
    """Run the load generator against ``server``; returns its result and
    the compiles counted inside the window."""
    cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
           "--port", str(server.port),
           "--traffic", str(spec.traffic_path(cell.traffic_name)),
           "--seed", str(seed), "--seconds", str(seconds),
           "--vocab", str(int(cell.config["vocab_size"]))]
    limit = seconds + LOADGEN_GRACE_S
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    killer = threading.Timer(limit, proc.kill)
    killer.start()
    marks, result = {}, None
    try:
        for line in proc.stdout:
            ev = json.loads(line)
            marks[ev["event"]] = compiles.n
            if ev["event"] == "window_start" and tracer is not None:
                tracer.start_at(ev["t"] + seconds - tracer.seconds)
            elif ev["event"] == "result":
                result = ev
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0 or result is None:
        raise RuntimeError(f"load generator exited {rc} without a result")
    in_window = marks.get("window_end", 0) - marks.get("window_start", 0)
    return result, in_window


def counts(run: Run):
    """(attempted, failed): requests due in the window or still in flight
    when it opened; failed are those refused or ended with an error."""
    t0, t1 = run.window
    live = [r for r in run.requests if r["due"] < t1 and (
        r["due"] >= t0 or not (r["done"] and r["t"] and r["t"][-1] < t0))]
    failed = [r for r in live if r["status"] not in (None, 200)
              or r["error"] is not None]
    return len(live), len(failed)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def reference_model(cell):
    return spec.architecture("models", cell.config["reference"])


def build(cell, model, seed, compiles):
    """Weights drawn on the device, the server built and warmed.  A
    configuration the program does not build fails first, before a weight
    is drawn."""
    import jax
    import numpy as np
    from repro.obs.metrics import MetricsRegistry
    cfg = cell.config
    model_spec = serve.program_spec(cfg)
    t = time.monotonic()
    params = {k: np.asarray(v) for k, v in
              jax.device_get(model.make_weights(cfg, seed)).items()}
    note(f"weights: {sum(a.nbytes for a in params.values())} bytes drawn on "
         f"the device in {time.monotonic() - t:.1f} s")
    t = time.monotonic()
    metrics = MetricsRegistry()
    server = serve.build(cfg, model_spec, params, metrics)
    del params
    note(f"server built in {time.monotonic() - t:.1f} s; device memory "
         f"{jax.devices()[0].memory_stats()}")
    t = time.monotonic()
    serve.warm(server, int(cell.traffic["prompt_len"]),
               int(cfg["vocab_size"]), seed)
    note(f"warmed in {time.monotonic() - t:.1f} s; {compiles.n} compiles "
         f"so far")
    return server, serve.instrument(server, metrics)


def window(server, recorder, cell, seed, seconds, compiles, tracer=None,
           t_start=T_START, peak=None):
    """The traffic over the window, against a started server."""
    import jax
    result, window_compiles = drive(server, cell, seed, seconds, tracer,
                                    compiles)
    run = Run(cfg=cell.config, t_start=t_start, window=tuple(result["window"]),
              requests=result["requests"], recorder=recorder,
              memory=jax.devices()[0].memory_stats() or {}, peak=peak)
    ticks = sum(1 for t in recorder.ticks if run.inside(t[1]))
    prefills = sum(1 for p in recorder.prefills if run.inside(p[1]))
    note(f"window: {run.window_s:.3f} s, {run.tokens_in_window()} tokens "
         f"received, {ticks} decode ticks, {prefills} prefills, "
         f"{window_compiles} compiles inside it")
    return run


def freed() -> None:
    """Collect the program's device state once the caller has dropped the
    server, so that the reference fits."""
    import jax
    gc.collect()
    note(f"device memory after freeing the server: "
         f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')} "
         f"bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny configuration on whatever backend JAX finds")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also write the trace's extracted events here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, rehearse=args.rehearse)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell.chips):
        note(f"perfbench: the cell needs {cell.chips} TPU chip(s); JAX "
             f"found {len(devices)} {dev.platform} device(s). Nothing run.")
        return NO_CHIP
    peak = None if args.rehearse else spec.peaks(dev.device_kind)
    if args.rehearse:
        note("REHEARSAL: tiny configuration, not a chip run")
    else:  # a CPU rehearsal keeps nothing on disk
        note(f"compile cache: {enable_compile_cache()}")
    import numpy as np
    model = reference_model(cell)
    compiles = CompileCounter()
    tracer = Tracer(min(TRACE_S, args.seconds / 2)) if args.trace else None
    server, recorder = build(cell, model, args.seed, compiles)
    server.start_in_thread()
    try:
        run = window(server, recorder, cell, args.seed, args.seconds,
                     compiles, tracer, peak=peak)
    finally:
        serve.stop(server)
    if tracer is not None:
        run.trace = tracer.reduce(args.keep_trace)
        run.host_end = tracer.on
        note(f"host window: {run.host_window[1] - run.window[0]:.3f} s, "
             f"then the profiler")
    values = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            values[m.name] = {"value": float(v), "unit": m.unit}
    attempted, failed = counts(run)
    note(f"{attempted} requests attempted, {failed} failed")

    # the reference, on the chip once the program is gone
    sample = correct.draw_sample(run.requests, args.seed, SAMPLE_REQUESTS)
    prog = correct.program_rows(run.recorder, sample)
    numbers = {"stream_faults": correct.stream_faults(run.requests)}
    run.recorder = None
    del server, recorder
    freed()
    t = time.monotonic()
    if sample:
        weights = model.make_weights(cell.config, args.seed)
        ref = correct.reference(cell.config, model, weights, sample,
                                int(cell.config["serve"]["max_len"]))
        del weights
        numbers.update(correct.logit_numbers(
            sample, ref, [np.asarray(r["tokens"]) for r in sample], prog))
    ok, checks = correct.judge(numbers, cell.config["correct"])
    ok = ok and bool(sample)
    note(f"reference over {len(sample)} requests, "
         f"{sum(len(r['tokens']) for r in sample)} served tokens, in "
         f"{time.monotonic() - t:.1f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory.get("peak_bytes_in_use")}
    line = {"correct": ok, "attempted": attempted, "failed": failed,
            "metrics": values, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
        note(f"idle by host state: {run.trace['idle_by_host_state']}")
    if args.rehearse:
        line["rehearsal"] = True
    line["checks"] = checks
    for name, c in checks.items():
        note(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
