"""The benchmark's arithmetic on hand-made inputs: percentiles and the rate
over a window with a stall in it, the traffic decks, the count functions
against numbers worked by hand at Qwen3-8B widths, the peaks table, and the
trace reduction on hand-made events and on the small trace recorded on a
v5e (``data/tiny_v5e.xplane.pb``)."""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness, peaks, stats, traffic  # noqa: E402
from benchmark import trace as tr  # noqa: E402
from benchmark.counts import Counts, qwen3_dense  # noqa: E402

counts = Counts(qwen3_dense)  # what a reader sees as ``run.counts``

CFG = json.loads((REPO / "benchmark/configs/qwen3-8b-d24.json").read_text())
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


# ------------------------------------------------------------------- stats


def _req(submit, times, max_new=None, steps=None, reason="ok"):
    r = stats.ReqLog(0, 8, max_new if max_new is not None else len(times), submit)
    r.token_t = list(times)
    r.tokens = list(range(len(times)))
    r.token_step = list(steps) if steps else list(range(len(times)))
    r.finish_reason = reason
    r.finish_t = times[-1] if times else None
    return r


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 5.5), (90, 9.1), (100, 10.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile(range(1, 11), q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 90) is None


def _steady(stall: float):
    """Ten requests, one a second, four tokens each 0.1 s apart; the server
    stalls for ``stall`` seconds at t = 5 (everything after moves out)."""
    reqs = []
    for i in range(10):
        sub = float(i)
        times = [sub + 0.2 + 0.1 * j for j in range(4)]
        times = [t + (stall if t >= 5.0 else 0.0) for t in times]
        reqs.append(_req(sub, times, steps=range(4 * i, 4 * i + 4)))  # a token a burst
    return reqs


def test_a_stall_lowers_the_rate_and_raises_the_tails():
    a, b = 0.0, 10.0
    calm, stalled = _steady(0.0), _steady(3.0)
    assert stats.out_tokens_per_s(calm, a, b) == pytest.approx(4.0)
    # 12 tokens fall out, but for the part of the next one's 0.7 s that lies inside
    assert stats.out_tokens_per_s(stalled, a, b) == pytest.approx((28 + 0.5 / 0.7) / 10)
    p = lambda rs: stats.percentile(stats.ttft_ms(rs, a, b, 14.0), 90)
    assert p(calm) == pytest.approx(200.0)
    assert p(stalled) == pytest.approx(3200.0)
    assert stats.percentile(stats.tpot_ms(stalled, a, b), 90) == pytest.approx(100.0, rel=1e-6)


def test_only_tokens_inside_the_window_count_and_only_requests_submitted_in_it():
    reqs = [_req(-1.0, [-0.5, 0.5], steps=[1, 2]), _req(1.0, [1.5, 2.5, 11.0], steps=[3, 4, 7]),
            _req(10.5, [10.7, 10.9], steps=[5, 6])]
    # half of the burst that straddles the opening, two whole, and 7.5 s of the
    # 8.2 s that the burst straddling the close took
    assert stats.out_tokens_per_s(reqs, 0.0, 10.0) == pytest.approx((0.5 + 2 + 7.5 / 8.2) / 10)
    assert stats.tokens_between(reqs, 0.0, 10.0) == 3
    assert stats.ttft_ms(reqs, 0.0, 10.0, 12.0) == pytest.approx([500.0])
    assert stats.tpot_ms(reqs, 0.0, 10.0) == pytest.approx([4750.0])


def test_the_rate_does_not_jump_by_a_burst_with_the_instant_of_the_close():
    """Bursts of 32 tokens every 0.2 s: whole bursts counted, a window that
    closes just before or just after one differs by 32 tokens; laid over
    the time they took, by next to nothing. A stall before the close
    still shows in full."""
    burst = lambda k: _req(0.0, [0.2 * (k + 1)] * 32, steps=[k] * 32)
    reqs = [burst(k) for k in range(60)]
    before, after = (stats.out_tokens_per_s(reqs, 0.0, c) for c in (9.999, 10.001))
    assert before == pytest.approx(160.0, rel=1e-3) and after == pytest.approx(160.0, rel=1e-3)
    assert (stats.tokens_between(reqs, 0.0, 10.001)
            - stats.tokens_between(reqs, 0.0, 9.999)) == 32
    late = [burst(k) for k in range(40)] + [_req(0.0, [12.0] * 32, steps=[40] * 32)]
    assert stats.out_tokens_per_s(late, 0.0, 10.0) == pytest.approx((40 * 32 + 32 * 2 / 4) / 10)


@pytest.mark.parametrize("req", [
    _req(1.0, [], max_new=4, reason=None),            # no first token at the drain's end
    _req(1.0, [1.2], max_new=4, reason="deadline"),   # failed
])
def test_a_request_that_missed_counts_at_the_drains_end(req):
    assert stats.ttft_ms([req], 0.0, 10.0, 12.0) == pytest.approx([11000.0])
    assert not req.ok


def test_a_rejected_request_counts_at_the_drains_end():
    r = _req(2.0, [], max_new=4, reason=None)
    r.rejected = "queue_full"
    assert stats.ttft_ms([r], 0.0, 10.0, 12.0) == pytest.approx([10000.0])


def test_burst_periods_leave_first_tokens_out():
    a = _req(0.0, [0.1, 0.30, 0.50], steps=[1, 3, 5])
    b = _req(0.0, [0.2, 0.31, 0.52], steps=[2, 3, 5])
    assert stats.burst_periods_ms([a, b], 1, 5) == pytest.approx([210.0])
    assert stats.burst_periods_ms([a, b], 1, 4) == []


# ----------------------------------------------------------------- traffic


def _client(mix, seed, index):
    return traffic.Client(mix, seed, index, 151936)


@pytest.mark.parametrize("name", ["chat", "doc"])
def test_every_seed_deals_the_same_sizes_in_another_order_with_other_tokens(name):
    mix = json.loads((REPO / f"benchmark/traffic/{name}.json").read_text())
    deck = traffic.deck(mix)
    assert len(deck) == mix["deck"]
    assert {p for p, _ in deck} == set(mix["prompt_len"]["values"])
    assert {n for _, n in deck} == set(mix["max_new"]["values"])
    dealt = []
    for seed in (1, 2**31 + 11):
        c = _client(mix, seed, 3)
        cards = [c.next() for _ in range(2 * mix["deck"])]
        for half in (cards[: mix["deck"]], cards[mix["deck"]:]):  # every pass deals the deck
            assert sorted((len(p), n) for p, n in half) == sorted(deck)
        assert all(0 <= t < 151936 for p, _ in cards for t in p)
        dealt.append(cards)
    sizes = lambda cards: [(len(p), n) for p, n in cards]
    assert sizes(dealt[0]) != sizes(dealt[1])  # the order is the seed's
    again = _client(mix, 1, 3)
    assert [again.next() for _ in range(2 * mix["deck"])] == dealt[0]
    # dealt in hands: every run of ``hand`` cards holds each new-token count once
    k = mix["hand"]
    assert k == len(mix["max_new"]["values"])
    for cards in dealt:
        for i in range(0, len(cards), k):
            assert sorted(n for _, n in cards[i:i + k]) == sorted(mix["max_new"]["values"])


@pytest.mark.parametrize("name", ["chat", "doc"])
def test_hands_hold_prompt_lengths_from_across_the_deck(name):
    mix = json.loads((REPO / f"benchmark/traffic/{name}.json").read_text())
    hands = traffic.hands(mix)
    assert sorted(c for h in hands for c in h) == sorted(traffic.deck(mix))
    tokens = [sum(p for p, _ in h) for h in hands]
    assert max(tokens) <= 2.1 * min(tokens)  # no hand of long prompts only
    with pytest.raises(ValueError):
        traffic.hands(dict(mix, hand=mix["hand"] + 1))


def test_the_closed_loop_answers_the_clients_that_finished():
    from benchmark.loops import closed

    mix = json.loads((REPO / "benchmark/traffic/chat.json").read_text())
    src = closed.source(mix, 7, 151936)
    first = src.due(0.0, None)
    assert [c for c, _, _ in first] == list(range(mix["clients"]))
    assert src.due(1.0, []) == []
    again = src.due(2.0, [5, 2])
    assert [c for c, _, _ in again] == [5, 2]
    want = _client(mix, 7, 5)
    want.next()
    assert again[0][1:] == want.next()


def test_chat_deck_keeps_the_declared_weights():
    mix = json.loads((REPO / "benchmark/traffic/chat.json").read_text())
    lens = [p for p, _ in traffic.deck(mix)]
    assert [lens.count(v) for v in (64, 128, 256, 512)] == [6, 6, 5, 3]
    assert traffic.prompt_lengths(mix) == [64, 128, 256, 512]


def test_sampled_traffic_is_refused():
    mix = json.loads((REPO / "benchmark/traffic/chat.json").read_text())
    traffic.check(mix)
    with pytest.raises(ValueError):
        traffic.check(dict(mix, sampling="top_p"))


# ------------------------------------------------------------------ counts


def test_weights_and_kv_by_hand():
    # qkv 4096x6144, o 4096x4096, gate/up/down 3 x 4096x12288
    assert counts.layer_weight_elems(CFG) == 25_165_824 + 16_777_216 + 150_994_944
    assert counts.matmul_weight_elems(CFG) == 24 * 192_937_984 + 4096 * 151_936
    assert counts.kv_bytes_per_token(CFG) == 24 * 2 * 8 * 128 * 2


def test_one_1024_token_prefill_by_hand():
    w = counts.prefill(CFG, 1024)
    matmul = 2 * 1024 * 24 * 192_937_984 + 2 * 4096 * 151_936
    attention = 24 * 2 * 32 * 128 * 1024 * 1025
    assert w["flops"] == matmul + attention == 9_690_892_206_080
    assert w["bytes"] == 2 * 5_252_841_472 + 1024 * 98_304
    least = counts.least_seconds(w, V5E)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(9_690_892_206_080 / 197e12)  # 49.2 ms


def test_one_decode_step_by_hand():
    w = counts.decode_steps(CFG, 1, [513, 100, 1, 2000])
    assert w["flops"] == 2 * 4 * 5_252_841_472 + 4 * 24 * 32 * 128 * 2614
    assert w["bytes"] == 2 * 5_252_841_472 + 2614 * 98_304 + 4 * 98_304
    least = counts.least_seconds(w, V5E)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(10_763_042_816 / 819e9)  # 13.1 ms
    quarter = counts.per_chip(w, 4)
    assert quarter["bytes"] == w["bytes"] / 4 and quarter["flops"] == w["flops"] / 4


def test_weights_are_read_once_a_step_whatever_the_batch():
    one = counts.decode_steps(CFG, 8, [100] * 8)
    four = counts.decode_steps(CFG, 8, [100] * 32)
    assert four["bytes"] - one["bytes"] == 24 * 100 * 98_304 + 24 * 98_304
    assert four["flops"] == 4 * one["flops"]


def test_the_attention_kernels_by_hand():
    # one decode step of four rows: K/V of 2614 live positions, 4 rows' q and output
    w = counts.paged_flash_decode(CFG, [513, 100, 1, 2000])
    assert w["flops"] == 4 * 24 * 32 * 128 * 2614
    assert w["bytes"] == 2614 * 98_304 + 4 * 24 * 2 * 32 * 128 * 2
    assert w["bytes"] < counts.decode_steps(CFG, 1, [513, 100, 1, 2000])["bytes"]
    assert counts.least_seconds(w, V5E)["bound"] == "memory"
    # a 1024-token prompt: the causal half, q and output 32 heads, K and V 8 heads
    w = counts.flash_attention(CFG, 1024)
    assert w["flops"] == 24 * 2 * 32 * 128 * 1024 * 1025
    assert w["bytes"] == 1024 * (98_304 + 24 * 2 * 32 * 128 * 2)
    assert w["flops"] < counts.prefill(CFG, 1024)["flops"]
    assert counts.least_seconds(w, V5E)["bound"] == "compute"


def test_the_common_arithmetic_is_no_architectures_to_replace():
    class Arch:
        per_chip = least_seconds = prefill = staticmethod(lambda *a: "the architecture's")

    both = Counts(Arch)
    assert both.prefill({}, 1) == "the architecture's"
    assert both.per_chip({"flops": 8.0}, 4) == {"flops": 2.0}
    assert both.least_seconds({"flops": 197e12, "bytes": 0.0}, V5E)["seconds"] == 1.0
    with pytest.raises(AttributeError):
        both.decode_steps


def test_the_toys_second_architecture_counts_its_own_block():
    toy = REPO / "tests/benchmark/toy/BENCHMARK.json"
    moe = harness.load_cell(toy, "toy-moe.toy-short", root=REPO)
    dense = harness.load_cell(toy, "toy-dense.toy-chat", root=REPO)
    # attention 64x512 + 256x64, router 64x8, two experts of 3 x 64x48; head 64x256
    per_token = 2 * (32_768 + 16_384 + 512 + 2 * 9_216) + 16_384
    assert moe.counts.token_weight_elems(moe.cfg) == per_token == 152_576
    w = moe.counts.decode_steps(moe.cfg, 1, [10, 20])
    assert w["flops"] == 2 * 2 * per_token + 4 * 2 * 8 * 32 * 30
    assert w["bytes"] == 4 * per_token + 32 * 2 * 2 * 4 * 32 * 4
    assert w != dense.counts.decode_steps(dense.cfg, 1, [10, 20])
    assert moe.counts.per_chip(w, 2)["flops"] == w["flops"] / 2


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# ------------------------------------------------------------------- trace


def _planes():
    E = tr.Ev
    dev = [
        E("while.1", 100, 600),        # holds the two fusions below
        E("fusion.1", 100, 200),
        E("fusion.2", 400, 300),
        E("all-reduce.3", 900, 100),
        E("fusion.1", 1500, 100),
    ]
    host = [E("server.step", 0, 1200), E("PjitFunction(step)", 50, 400),
            E("server.step", 1300, 500)]
    programs = [E("jit_decode_chunk(123)", 100, 600), E("jit_chunk_fn(77)", 900, 100),
                E("jit_decode_chunk(123)", 1500, 100), E("jit_decode_chunk(9)", 1900, 50)]
    return {
        "/device:TPU:0": {"XLA Ops": dev, "XLA Modules": programs,
                          "Steps": [E("7", 0, 2000)]},
        "/device:TPU:1": {"XLA Ops": [E("fusion.1", 100, 200)]},
        "/host:CPU": {"python3": host},
    }


def test_busy_union_and_own_times():
    dev = _planes()["/device:TPU:0"]["XLA Ops"]
    assert tr.union(dev) == [[100, 700], [900, 1000], [1500, 1600]]
    assert tr.busy_ns(dev) == 800
    own = tr.self_times(dev)
    assert own == {"while.1": 100, "fusion.1": 300, "fusion.2": 300, "all-reduce.3": 100}


def test_reduce_reads_the_busiest_device():
    r = tr.reduce(_planes())
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx((800 + 200) / 2 * 1e-9)
    assert r["idle_pct"] == pytest.approx(60.0)
    assert r["device_ops"][:2] == [["fusion.1", pytest.approx(300e-9)],
                                   ["fusion.2", pytest.approx(300e-9)]]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # 0-100 and 700-900 and 1000-1200.. fall under a step; 1200-1300 between steps
    assert gaps["PjitFunction(step)"] == pytest.approx(100e-9)
    assert sum(gaps.values()) == pytest.approx(1200e-9)


def test_program_seconds_by_the_programs_whole_name():
    import re

    r = tr.reduce(_planes())
    assert r["steps"] == [[0.0, pytest.approx(1200e-9)], [pytest.approx(1300e-9),
                                                          pytest.approx(1800e-9)]]
    assert [n for n, _, _ in r["programs"]] == ["jit_decode_chunk", "jit_chunk_fn",
                                                 "jit_decode_chunk", "jit_decode_chunk"]
    spent, n = tr.program_seconds(r, re.compile(r"jit_decode_chunk\w*"))
    assert (spent, n) == (pytest.approx(750e-9), 3)
    assert tr.program_seconds(r, re.compile("jit_chunk"))[1] == 0  # the whole name
    assert tr.program_seconds(r, re.compile("jit_chunk_fn")) == (pytest.approx(100e-9), 1)
    assert tr.program_totals(r)["jit_decode_chunk"] == [pytest.approx(750e-9), 3]
    assert tr.program_seconds(dict(r, programs=[]), re.compile(".*")) == (0.0, 0)


def test_op_seconds_by_the_operations_name_whatever_its_rank():
    import re

    planes = _planes()
    dev = planes["/device:TPU:0"]["XLA Ops"]
    kernel = "%paged_flash_decode.9 = (bf16[4,8,4,128]{3,2,1,0}, f32[4,8,4,1]{3,2,1,0}) custom-call(%p)"
    dev += [tr.Ev(kernel, 1650, 2), tr.Ev(kernel, 1660, 3),
            tr.Ev("%paged_flash_decode_quant.2 = bf16[4] custom-call(%p)", 1670, 1)]
    dev += [tr.Ev(f"%fusion.{100 + i} = bf16[4] fusion(%p)", 1700 + 10 * i, 8) for i in range(12)]
    r = tr.reduce(planes)
    assert tr.op_name(kernel) == "paged_flash_decode" and tr.op_name("fusion.1") == "fusion"
    assert tr.op_name("%copy-start.1 = (bf16[2]) copy-start(%x)") == "copy-start"
    # the result line keeps ten; the readers get all, with their executions
    assert len(r["device_ops"]) == 10 and not any("paged" in n for n, _ in r["device_ops"])
    assert len(r["ops"]) == 4 + 2 + 12 and r["ops"]["fusion.1"] == [pytest.approx(300e-9), 2]
    assert sum(s for s, _ in r["ops"].values()) == pytest.approx(r["busy_s"] * 2 - 200e-9)
    assert tr.op_seconds(r, re.compile("paged_flash_decode")) == (pytest.approx(5e-9), 2)
    assert tr.op_seconds(r, re.compile(r"paged_flash_decode\w*")) == (pytest.approx(6e-9), 3)
    assert tr.op_seconds(r, re.compile("flash_decode")) == (0.0, 0)  # the whole name
    assert tr.op_seconds(r, re.compile("fusion")) == (pytest.approx((600 + 96) * 1e-9), 15)


def test_a_trace_with_no_device_operation_is_refused():
    planes = _planes()
    del planes["/device:TPU:0"], planes["/device:TPU:1"]
    with pytest.raises(ValueError):
        tr.reduce(planes)


def test_recorded_v5e_trace():
    """Three calls of a small jitted loop, recorded on one v5e with the
    harness's own profiler options and step annotation."""
    planes = tr.load(str(REPO / "tests/benchmark/data/tiny_v5e.xplane.pb"))
    assert any(p.startswith("/device:TPU:") for p in planes)
    r = tr.reduce(planes)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_pct"] < 100
    assert [n for n, _, _ in r["programs"]] == ["jit_step"] * 3 and len(r["steps"]) == 3
    import re
    spent, n = tr.program_seconds(r, re.compile("jit_step"))
    # a program's event spans its operations and the gaps between them
    assert n == 3 and r["busy_s"] <= spent <= 1.02 * r["busy_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    # every operation of the trace by its name: nine fusions in three runs of the loop
    assert tr.op_seconds(r, re.compile("convolution_tanh_fusion"))[1] == 9
    assert tr.op_seconds(r, re.compile("copy-start"))[1] == 6
    own, ran = tr.op_seconds(r, re.compile(".*"))
    assert ran == 36 and own == pytest.approx(r["busy_s"], rel=1e-3)
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"] * 1.0001
    assert any("server.step" in k or "outside" in k for k, _ in r["idle_gaps"])


def test_labels_drop_layouts():
    text = ("%fusion.9 = bf16[4,4096]{1,0:T(4,128)(2,1)S(1)} fusion(bf16[4,4096]{1,0} "
            "%all-reduce.3, s32[]{:T(128)} %p), kind=kOutput")
    assert tr.short(text) == ("%fusion.9 = bf16[4,4096] fusion(bf16[4,4096] "
                              "%all-reduce.3, s32[] %p), kind=kOutput")
    assert len(tr.short("x" * 500)) == 160
    assert tr.program("jit_decode_chunk(13210887796676533005)") == "jit_decode_chunk"
