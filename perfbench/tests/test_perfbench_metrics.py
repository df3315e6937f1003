"""CPU tests of the benchmark's arithmetic: the metric readers, the counts
of operations and bytes, the closed-loop generator, and the reduction from
a profiler trace (on a synthetic trace and on one recorded on the chip)."""

import asyncio
import gzip
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import client, flops, spec, trace_reduce  # noqa: E402
from perfbench.loadgen import IO  # noqa: E402
from perfbench.record import Run  # noqa: E402
from perfbench.serve import DECODE, PREFILL  # noqa: E402

V5E = spec.peaks("TPU v5 lite")
DENSE = spec.architecture("counts", "dense_gqa")


def config(name, **changes):
    return {**spec.load_json(ROOT / "perfbench" / "configs" / f"{name}.json"),
            **changes}


def req(due, t=(), status=200, error=None, prompt_len=4, max_tokens=None):
    t = list(t)
    return {"prompt": list(range(prompt_len)), "due": due, "sent": due,
            "status": status, "error": error, "done": error is None,
            "max_tokens": max_tokens or len(t), "tokens": [1] * len(t),
            "index": list(range(len(t))), "t": t}


def run_of(requests, window=(10.0, 20.0), cfg=None, peak=None, ticks=(),
           prefills=(), trace=None, host_end=None):
    class Rec:
        pass
    rec = Rec()
    rec.ticks = list(ticks)
    rec.prefills = list(prefills)
    return Run(cfg=cfg or {}, t_start=1.0, window=window, requests=requests,
               recorder=rec, trace=trace, memory={}, peak=peak,
               host_end=host_end)


# -- the end-to-end readers -------------------------------------------------


def test_tokens_per_second_over_the_window():
    r1 = req(0.0, t=[9.9, 10.0, 15.0, 19.999, 20.0])
    r2 = req(11.0, t=[11.0 + 0.5 * i for i in range(30)])   # up to 25.5
    run = run_of([r1, r2], window=(10.0, 20.0))
    # r1: 10.0, 15.0, 19.999; r2: 11.0 .. 19.5 (18 tokens)
    assert run.tokens_in_window() == 21
    assert spec.reader("output_tokens_per_s")(run) == pytest.approx(2.1)


def test_setup_runs_to_the_window_start():
    assert spec.reader("setup_s")(run_of([], window=(42.5, 52.5))) == \
        pytest.approx(41.5)


# -- per-layer readers ------------------------------------------------------


def tick(t0, t1, batch, live, hist_sum, hist_count):
    return (t0, t1, batch, live, hist_sum, hist_count)


def test_decode_readers_take_the_window_ticks():
    ticks = [tick(8.0, 9.0, 4, 400, 1.0, 1),      # before the window
             tick(10.0, 11.0, 8, 800, 2.0, 2),
             tick(11.0, 12.5, 6, 600, 3.5, 3),
             tick(19.5, 21.0, 8, 900, 5.0, 4)]    # ends after it
    run = run_of([], ticks=ticks)
    assert spec.reader("decode_batch_mean.batch")(run) == pytest.approx(7.0)
    # the histogram's sum and count across the window: (3.5 - 1) / (3 - 1)
    assert spec.reader("decode_tick_ms.chat")(run) == pytest.approx(1250.0)
    assert spec.reader("decode_batch_mean.chat")(run_of([])) is None
    assert spec.reader("decode_tick_ms.batch")(run_of([])) is None


def test_host_window_stops_where_the_profiler_starts():
    ticks = [tick(10.0, 11.0, 8, 800, 1.0, 1),
             tick(11.0, 12.0, 8, 800, 2.0, 2),
             tick(12.0, 14.0, 6, 600, 4.0, 3),    # ends after the profiler
             tick(14.0, 16.0, 4, 400, 6.0, 4)]
    run = run_of([], ticks=ticks, host_end=13.0)
    assert run.host_window == (10.0, 13.0)
    assert [t[1] for t in run.host_ticks()] == [11.0, 12.0]
    assert spec.reader("decode_batch_mean.batch")(run) == pytest.approx(8.0)
    # (2.0 - 0) / (2 - 0): the tick that ended at 11.0 is the first counted
    assert spec.reader("decode_tick_ms.batch")(run) == pytest.approx(1000.0)
    # without a profiler the host window is the whole window
    whole = run_of([], ticks=ticks)
    assert whole.host_window == whole.window
    assert spec.reader("decode_batch_mean.batch")(whole) == pytest.approx(6.5)
    # a profiler that started after the window leaves the window whole
    assert run_of([], host_end=25.0).host_window == (10.0, 20.0)


def test_prefill_readers_take_the_host_window():
    prefills = [(9.5, 10.5, 512),     # straddles the window's start
                (12.0, 13.0, 512),
                (14.0, 15.5, 512),
                (17.5, 18.5, 512)]    # after the profiler started at 17.0
    run = run_of([], prefills=prefills, host_end=17.0)
    # three end in [10, 17): 1.0, 1.0 and 1.5 s
    assert spec.reader("prefill_ms.batch")(run) == pytest.approx(3500.0 / 3)
    # clipped to [10, 17): 0.5 + 1.0 + 1.5 of 7 s
    assert spec.reader("prefill_share.batch")(run) == pytest.approx(
        100.0 * 3.0 / 7.0)
    whole = run_of([], prefills=prefills)
    assert spec.reader("prefill_share.batch")(whole) == pytest.approx(
        100.0 * 4.0 / 10.0)
    assert spec.reader("prefill_ms.batch")(run_of([])) is None
    assert spec.reader("prefill_share.batch")(run_of([])) is None


def test_roofline_share_of_the_window_ticks():
    cfg = config("mistral-7b")
    ticks = [tick(10.0, 11.0, 8, 8 * 200, 0, 0),
             tick(11.0, 11.5, 4, 4 * 300, 0, 0)]
    run = run_of([], cfg=cfg, peak=V5E, ticks=ticks)
    least = sum(flops.least_time_s(*flops.decode_tick(cfg, b, live), V5E)
                for _, _, b, live, _, _ in ticks)
    share = spec.reader("decode_step_roofline.batch")(run)
    assert share == pytest.approx(100.0 * least / 1.5)
    assert 0.0 < share < 100.0
    assert spec.reader("decode_step_roofline.batch")(
        run_of([], cfg=cfg, peak=V5E)) is None
    assert spec.reader("decode_step_roofline.batch")(
        run_of([], cfg=cfg, ticks=ticks)) is None


def test_mfu_counts_prefill_then_decode_work():
    cfg = config("mistral-7b")
    r = req(9.0, t=[9.5, 10.5, 11.0], prompt_len=100)
    run = run_of([r], cfg=cfg, peak=V5E)
    work = (flops.decode_token_flops(cfg, 101)
            + flops.decode_token_flops(cfg, 102))
    assert spec.reader("mfu.batch")(run) == pytest.approx(
        100.0 * work / 10.0 / V5E["bf16_flops_per_s"])
    first = req(10.0, t=[10.5], prompt_len=100)
    assert spec.reader("mfu.batch")(run_of([first], cfg=cfg, peak=V5E)) == \
        pytest.approx(100.0 * flops.prefill_flops(cfg, 100) / 10.0
                      / V5E["bf16_flops_per_s"])
    # nothing to read is nothing, never a share of 0
    assert spec.reader("mfu.batch")(run_of([], cfg=cfg, peak=V5E)) is None
    # a traced run counts the tokens before the profiler, over that time
    traced = run_of([r], cfg=cfg, peak=V5E, host_end=11.0)
    assert spec.reader("mfu.batch")(traced) == pytest.approx(
        100.0 * flops.decode_token_flops(cfg, 101) / 1.0
        / V5E["bf16_flops_per_s"])


def test_trace_readers():
    trace = {"busy_s": 0.5, "idle_share": 0.95, "launches_per_tick": 2193.0}
    run = run_of([], trace=trace)
    assert spec.reader("device_idle_share.batch")(run) == pytest.approx(95.0)
    assert spec.reader("launches_per_tick.batch")(run) == 2193.0
    assert spec.reader("device_idle_share.chat")(run_of([])) is None
    assert spec.reader("launches_per_tick.batch")(run_of([])) is None
    busy_nothing = run_of([], trace={**trace, "busy_s": 0.0})
    assert spec.reader("device_idle_share.chat")(busy_nothing) is None


def test_hbm_peak():
    run = run_of([])
    assert spec.reader("hbm_peak_gib.batch")(run) is None
    run.memory = {"peak_bytes_in_use": 3 * 2 ** 30}
    assert spec.reader("hbm_peak_gib.batch")(run) == pytest.approx(3.0)


# -- operations and bytes, by hand -----------------------------------------


@pytest.mark.parametrize("name,layers,params", [
    ("mistral-7b", 6, 1_442_840_576),
    ("deepseek-7b", 3, 1_026_555_904),
    ("deepseek-7b", 2, 824_180_736),
])
def test_matrix_params_by_hand(name, layers, params):
    cfg = config(name, num_hidden_layers=layers)
    assert DENSE.matrix_params(cfg) == params


def test_configured_depths():
    assert config("mistral-7b")["num_hidden_layers"] == 6
    assert config("deepseek-7b")["num_hidden_layers"] == 2


def test_decode_tick_counts_by_hand():
    cfg = config("mistral-7b")
    # 6 layers: 32 heads of 128; KV per token 2 * 6 * 8 * 128 * 4 bytes
    assert DENSE.kv_bytes_per_token(cfg) == 49_152
    f, b = flops.decode_tick(cfg, 8, 1000)
    assert f == 2 * 8 * 1_442_840_576 + 4 * 6 * 32 * 128 * 1000
    assert b == 1_442_840_576 * 4 + 8 * 4096 * 4 + 49_152 * 1000
    assert 5.77e9 < 1_442_840_576 * 4 < 5.78e9
    ds = config("deepseek-7b", num_hidden_layers=3)
    assert DENSE.kv_bytes_per_token(ds) == 2 * 3 * 32 * 128 * 4
    assert 4.10e9 < DENSE.matrix_params(ds) * 4 < 4.11e9
    # at these batches the tick is bounded by bytes, not operations
    t = flops.least_time_s(f, b, V5E)
    assert t == pytest.approx(b / V5E["hbm_bytes_per_s"])


def test_prefill_flops_by_hand():
    cfg = config("deepseek-7b")
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    n = 512
    assert flops.prefill_flops(cfg, n) == (
        2 * n * 2 * layer + 4 * 2 * 32 * 128 * n * (n + 1) // 2
        + 2 * 102400 * 4096)


def test_counts_of_an_architecture_without_a_counts_module_are_an_error():
    cfg = config("mistral-7b", reference="no_such_architecture")
    with pytest.raises(FileNotFoundError, match="counts/no_such_arch"):
        flops.decode_tick(cfg, 8, 1000)
    with pytest.raises(FileNotFoundError, match="no_such_architecture"):
        flops.prefill_flops(cfg, 128)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9


# -- the closed-loop generator ----------------------------------------------


class InstantIO(IO):
    """Serves every request at once, with a token per millisecond."""

    def __init__(self):
        self.marks = []

    async def send(self, rec, on_first=None):
        await asyncio.sleep(0.001 * rec["max_tokens"])
        now = time.monotonic()
        if on_first is not None:
            on_first(now)
        n = rec["max_tokens"]
        rec.update(sent=now, status=200, done=True, tokens=[0] * n,
                   index=list(range(n)), t=[now] * n)
        return rec

    def mark(self, event):
        self.marks.append(event)


def closed_loop(seed, traffic):
    io = InstantIO()
    gen = spec.generator("closed_loop")
    res = asyncio.run(gen.run(traffic, seed, 1000, 0.2, io))
    assert io.marks == ["window_start", "window_end"]
    return res


def test_closed_loop_staggers_the_first_answers():
    traffic = spec.load_json(spec.traffic_path("batch-512"))
    gen = spec.generator("closed_loop")
    firsts = [gen.first_output_len(traffic, i) for i in range(8)]
    assert firsts == [4, 8, 12, 16, 20, 24, 28, 32]
    res = closed_loop(5, traffic)
    by_client = {}
    for r in res["requests"]:
        by_client.setdefault(r["client"], []).append(r)
    assert sorted(by_client) == list(range(8))
    for i, rs in by_client.items():
        assert rs[0]["max_tokens"] == firsts[i]
        assert all(r["max_tokens"] == 32 for r in rs[1:])
        assert all(len(r["prompt"]) == 512 for r in rs)
    t0, t1 = res["window"]
    assert t1 - t0 == pytest.approx(0.2)
    # the window opens at the last client's first token
    assert t0 == max(rs[0]["t"][0] for rs in by_client.values())


def test_closed_loop_gives_every_seed_the_same_work():
    traffic = spec.load_json(spec.traffic_path("rehearsal-closed_loop"))
    def firsts(seed):
        """Each client's first request: (max_tokens, prompt)."""
        out = {}
        for r in closed_loop(seed, traffic)["requests"]:
            assert len(r["prompt"]) == 16
            out.setdefault(r["client"], (r["max_tokens"], r["prompt"]))
        return out

    a, b = firsts(7), firsts(2 ** 33 + 5)
    assert [a[i][0] for i in range(4)] == [b[i][0] for i in range(4)] == \
        [2, 4, 6, 8]
    assert [a[i][1] for i in range(4)] != [b[i][1] for i in range(4)]
    # the same seed sends the same prompts, whatever the timing
    assert firsts(7) == a


# -- the trace reduction -----------------------------------------------------


def synthetic():
    ms = 1e6
    return {"window_ns": 100 * ms, "chips": ["/device:TPU:0"],
            "ops": [[0, "fusion.1", 10 * ms, 20 * ms],
                    [0, "fusion.2", 15 * ms, 30 * ms],
                    [0, "copy.3", 60 * ms, 70 * ms],
                    [0, "fusion.1", 95 * ms, 110 * ms]],
            "modules": [[0, "jit_gather(123)", 10 * ms, 30 * ms],
                        [0, "jit_gather(456)", 60 * ms, 70 * ms],
                        [0, "jit_add(7)", 95 * ms, 110 * ms]],
            "host": [[DECODE, 6 * ms, 50 * ms], [PREFILL, 55 * ms, 80 * ms],
                     [DECODE, 90 * ms, 100 * ms]]}


def test_reduce_synthetic_trace():
    r = trace_reduce.reduce(synthetic())
    # busy: [10, 30] + [60, 70] + [95, 100] clipped to the window
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["idle_share"] == pytest.approx(0.65)
    # idle gaps: [0,10] outside, [30,60] in a decode tick (midpoint 45),
    # [70,95] in a prefill (midpoint 82.5 is outside both: 80 < 82.5 < 90)
    assert r["idle_gaps"] == [["decode_tick", pytest.approx(0.03)],
                              ["outside", pytest.approx(0.025)],
                              ["outside", pytest.approx(0.01)]]
    # launches: ticks [6, 50] holds one start, [90, 100] one
    assert r["launches_per_tick"] == 1.0 and r["ticks_traced"] == 2
    names = dict((k, v) for k, v in r["device_ops"])
    assert names["jit_gather"] == pytest.approx(0.03)
    assert names["jit_add"] == pytest.approx(0.015)


def test_reduce_without_program_events_uses_operations():
    ev = synthetic()
    ev["modules"] = []
    r = trace_reduce.reduce(ev)
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["launches_per_tick"] is None
    assert dict((k, v) for k, v in r["device_ops"])["fusion"] == \
        pytest.approx(0.04)


def test_union_merges_and_clips():
    assert trace_reduce.union([(5, 8), (0, 2), (1, 3), (7, 12)], 1, 10) == \
        [(1, 3), (5, 10)]
    assert trace_reduce.op_family("fusion.12.3") == "fusion"
    assert trace_reduce.op_family("jit_gather(2739389586537468544)") == \
        "jit_gather"


def test_reduce_recorded_decode_trace():
    path = Path(__file__).parent / "fixtures" / "decode-trace.json.gz"
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    r = trace_reduce.reduce(ev)
    ticks = [(s, e) for n, s, e in ev["host"]
             if n == DECODE and s >= 0 and e <= ev["window_ns"]]
    assert len(ticks) == 1
    s, e = ticks[0]
    assert r["launches_per_tick"] == sum(
        1 for m in ev["modules"] if s <= m[2] <= e) == 2193
    assert 0.0 < r["busy_s"] < r["window_s"] == pytest.approx(1.2)
    assert r["busy_s"] <= sum(m[3] - m[2] for m in ev["modules"]) / 1e9
    assert 0.9 < r["idle_share"] < 0.95
    assert r["device_ops"][0][0] == "jit_gather"
    assert len(r["device_ops"]) == len(r["idle_gaps"]) == 10
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] + 1e-9
    idle = sum(r["idle_by_host_state"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
