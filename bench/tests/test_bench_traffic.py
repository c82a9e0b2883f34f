"""Traffic generators and the window's arithmetic: seeded determinism,
the open-loop rate, the generator's lateness, the window closing at the end
of the last step, tail latencies with failures as misses, and TPOT."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, spec
from bench.serve import Step
from bench.traffic.common import Item

VOCAB = 1000


def traffic(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json").read_text())


def source(name, seed, seconds=20.0):
    cell = SimpleNamespace(traffic=traffic(name), bench_dir=spec.BENCH_DIR)
    return spec.traffic_source(cell, seed, VOCAB, seconds)


def drain_items(src, until):
    return src.poll(until)


@pytest.mark.parametrize("name", ["topk.batch", "dense.vlmchat"])
def test_generators_are_a_function_of_the_seed(name):
    big = 2**33 + 7
    a, b, c = source(name, big), source(name, big), source(name, big + 1)
    ia, ib, ic = drain_items(a, 20.0), drain_items(b, 20.0), drain_items(c, 20.0)
    assert [(i.rid, i.due_rel, i.out_len, i.tokens.tolist()) for i in ia] == \
        [(i.rid, i.due_rel, i.out_len, i.tokens.tolist()) for i in ib]
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(ia, ic))
    assert all(0 <= int(t) < VOCAB for i in ia for t in i.tokens)


def test_closed_loop_sends_the_next_request_when_one_finishes():
    src = source("topk.batch", 3)
    first = src.poll(0.0)
    assert len(first) == 16 and {len(i.tokens) for i in first} == {320}
    assert src.poll(100.0) == []
    src.finished(first[0], 5.0)
    nxt = src.poll(5.0)
    assert len(nxt) == 1 and nxt[0].due_rel == 5.0 and nxt[0].out_len == 32
    src.close()
    src.finished(first[1], 6.0)
    assert src.poll(1e9) == [] and src.next_due() is None


def test_open_loop_rate_sizes_and_seed_only_reorders():
    spec_ = traffic("dense.vlmchat")
    seconds = 200.0
    a, b = source("dense.vlmchat", 1, seconds), source("dense.vlmchat", 2, seconds)
    ia, ib = a.poll(seconds), b.poll(seconds)
    # every request falls due inside the window, at the stated rate
    assert len(ia) == len(a.items) == len(ib) == round(spec_["rate_rps"] * seconds)
    assert max(i.due_rel for i in ia) < seconds
    # every seed draws from the same multiset of sizes and of gaps
    assert sorted((len(i.tokens), i.out_len) for i in a.items) == \
        sorted((len(i.tokens), i.out_len) for i in b.items)
    def gaps(items):
        return sorted(np.diff([0.0] + [i.due_rel for i in items]).round(9))

    assert gaps(a.items) == gaps(b.items)
    assert [i.due_rel for i in ia] != [i.due_rel for i in ib]
    assert {len(i.tokens) for i in ia} == {320, 576, 1088}
    assert all(8 <= i.out_len <= 128 for i in ia)
    assert np.median([i.out_len for i in ia]) == pytest.approx(32, abs=3)


def test_open_loop_warmup_covers_every_prompt_shape():
    assert sorted(len(i.tokens) for i in source("dense.vlmchat", 5).warmup()) == \
        [320, 576, 1088]


class FakeProgram:
    """Steps of fixed length; each step finishes the oldest live request."""

    def __init__(self, step_s):
        self.step_s, self.live, self.done, self.decode_masks = step_s, [], [], []
        self.engine = SimpleNamespace(stats=[])

    @property
    def idle(self):
        return not self.live

    def submit(self, item, due):
        sv = SimpleNamespace(item=item, due=due, submitted=harness.clock(),
                             finished=None, token_times=[])
        self.live.append(sv)
        return sv

    def step(self):
        t0 = harness.clock()
        while harness.clock() - t0 < self.step_s:
            pass
        t1 = harness.clock()
        sv = self.live.pop(0)
        sv.finished, sv.token_times = t1, [t1]
        return Step(t0, t1, 1), [sv]


class FakeSource:
    """Open loop: one request due every ``gap`` seconds."""

    def __init__(self, gap):
        self.gap, self.t, self.closed = gap, 0.0, False

    def poll(self, t_rel):
        out = []
        while not self.closed and self.t <= t_rel:
            out.append(Item(len(out), np.zeros(2, np.int32), 1, self.t))
            self.t += self.gap
        return out

    def next_due(self):
        return None if self.closed else self.t

    def finished(self, item, t_rel):
        pass

    def close(self):
        self.closed = True


def test_window_closes_at_the_end_of_the_last_step_begun_inside_it():
    prog = FakeProgram(step_s=0.12)
    win = harness.run_window(prog, FakeSource(gap=0.0001), 0.3, None)
    # steps begin at ~0, 0.12, 0.24: the last ends near 0.36, past the close
    assert win.steps[-1].begin < win.close <= win.steps[-1].end
    assert win.end == win.steps[-1].end and win.seconds == pytest.approx(0.36, abs=0.03)
    assert win.tokens == len(win.steps)
    # requests that came due during a step were handed over after it: late
    assert max(win.late_s) > 0.05


def test_an_idle_window_lasts_its_full_length():
    prog = FakeProgram(step_s=0.001)
    win = harness.run_window(prog, FakeSource(gap=0.1), 0.35, None)
    assert win.end == win.close and win.seconds == pytest.approx(0.35, abs=1e-6)
    assert len(win.requests) == 4  # due at 0, 0.1, 0.2, 0.3
    assert max(win.late_s) < 0.05


def served(due, times):
    return SimpleNamespace(due=due, finished=times[-1] if times else None,
                           token_times=times)


def test_tails_count_failed_requests_as_misses_and_tpot():
    ok = [served(0.0, [0.1 + 0.01 * k for k in range(11)]) for _ in range(19)]
    win = SimpleNamespace(requests=ok + [served(0.0, [])])
    got = harness.latency(win, t_final=10.0)
    # 20 requests, one never finished: the 95th percentile falls between
    # the last good TTFT (0.1 s) and the failure's 10 s wait
    assert got["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([0.1] * 19 + [10.0], 95))
    assert got["ttft_p95_ms"] > 100.0
    # TPOT: (last - first) / (n - 1) = 0.1 / 10
    win = SimpleNamespace(requests=ok)
    assert harness.latency(win, 10.0)["tpot_p95_ms"] == pytest.approx(10.0)
