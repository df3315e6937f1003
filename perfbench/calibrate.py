"""Readings that set the correctness limits.

    python3 perfbench/calibrate.py --workload mistral-7b.batch \
        --seeds 1,2,3 --seconds 30

For each seed, in one process: the cell's weights and traffic over a
short window, then the reference over the sample a run would check, and
the control: the same reference computed at the next precision below the
configuration's float32, one bf16 pass (``bf16``), whose own logits and
greedy tokens are read at the same positions.  Last, a planted fault: the
reference in the program's place with each served token altered where it
is produced (``altered_token``).  One JSON line per seed: ``{"seed",
"tokens", "program": {...}, "bf16": {...}, "altered_token": {...}}``.

Not part of a benchmark run; it runs on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as bench  # noqa: E402

from perfbench import correct, serve, spec  # noqa: E402


def emit(obj, out):
    line = json.dumps(obj)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def readings(cell, model, seed, seconds, compiles, out):
    import numpy as np
    t_start = time.monotonic()
    server, recorder = bench.build(cell, model, seed, compiles)
    server.start_in_thread()
    try:
        run = bench.window(server, recorder, cell, seed, seconds, compiles,
                           t_start=t_start)
    finally:
        serve.stop(server)
    sample = correct.draw_sample(run.requests, seed, bench.SAMPLE_REQUESTS)
    prog = correct.program_rows(recorder, sample)
    faults = correct.stream_faults(run.requests)
    del server, recorder
    run.recorder = None
    bench.freed()
    weights = model.make_weights(cell.config, seed)
    max_len = int(cell.config["serve"]["max_len"])
    ref = correct.reference(cell.config, model, weights, sample, max_len)
    line = {"seed": seed, "requests": len(sample),
            "tokens": sum(len(r["tokens"]) for r in sample),
            "stream_faults": faults,
            "setup_s": run.window[0] - t_start,
            "memory_peak_bytes": run.memory.get("peak_bytes_in_use"),
            "program": correct.logit_numbers(
                sample, ref, [np.asarray(r["tokens"]) for r in sample],
                prog)}
    low = correct.reference(cell.config, model, weights, sample, max_len,
                            "bf16")
    line["bf16"] = correct.logit_numbers(
        sample, ref, [x.argmax(axis=1) for x in low], low)
    vocab = int(cell.config["vocab_size"])
    line["altered_token"] = correct.logit_numbers(
        sample, ref, [(x.argmax(axis=1) + 1) % vocab for x in ref], ref)
    del weights
    emit(line, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="also append the lines to this file")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, rehearse=args.rehearse)
    import jax
    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("calibrate: no TPU", file=sys.stderr)
            return bench.NO_CHIP
        bench.enable_compile_cache()
    model = bench.reference_model(cell)
    compiles = bench.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        readings(cell, model, seed, args.seconds, compiles, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
