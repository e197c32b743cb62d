"""End-to-end arithmetic and the traffic generator."""

import numpy as np

from bench import common, e2e, traffic_gen
from bench.drive import Client, Served


def rec(due, times, max_new=None):
    r = Served(0, 10, max_new or len(times), due)
    r.times = list(times)
    r.tokens = [1] * len(times)
    return r


def test_p95_is_over_every_request_and_every_gap():
    # 19 fast requests and one slow: the slow one is the tail, not averaged away
    served = [rec(0.0, [0.1, 0.2]) for _ in range(19)] + [rec(0.0, [2.0, 2.1])]
    ttft, censored = e2e.ttft(served, 0.0, 10.0)
    assert len(ttft) == 20 and censored == 0
    assert e2e.p95(ttft) == np.percentile([0.1] * 19 + [2.0], 95)
    gaps = e2e.itl(served, 0.0, 10.0)
    assert len(gaps) == 20  # one gap per two-token request, all kept
    many = rec(0.0, np.arange(0, 1.0, 0.01))  # 99 gaps of 10 ms
    assert len(e2e.itl(served + [many], 0.0, 10.0)) == 20 + 99


def test_request_without_first_token_is_censored_at_close():
    served = [rec(1.0, [1.5]), rec(4.0, [], max_new=5)]
    ttft, censored = e2e.ttft(served, 0.0, 10.0)
    assert censored == 1
    assert sorted(ttft) == [0.5, 6.0]  # waited 6 s by the close, not dropped


def test_only_requests_due_in_the_window_count():
    served = [rec(-1.0, [0.5]), rec(2.0, [2.5]), rec(10.0, [10.5])]
    ttft, _ = e2e.ttft(served, 0.0, 10.0)
    assert ttft == [0.5]


def test_tokens_per_second_counts_tokens_inside_the_window():
    served = [rec(0.0, [-0.5, 0.5, 1.5, 2.5])]
    assert e2e.output_tokens(served, 0.0, 2.0) == 2


class FakeEngine:
    """Takes ``tick_s`` per step; each admitted request gets one token
    per step.  ``stall`` adds seconds to one step."""

    def __init__(self, clock, tick_s=0.01, stall_at=None, stall_s=0.0):
        self.clock = clock
        self.tick_s, self.stall_at, self.stall_s = tick_s, stall_at, stall_s
        self.queue, self.live, self.uid, self.steps = [], {}, 0, 0
        self.scheduler = self

    @property
    def pending(self):
        return self.queue

    def done(self):
        return not self.queue and not self.live

    def submit(self, prompt, max_new):
        self.uid += 1
        self.queue.append((self.uid, max_new))
        return self.uid

    def step(self):
        from repro.serve.engine import TokenEvent

        self.clock.t += self.tick_s
        if self.steps == self.stall_at:
            self.clock.t += self.stall_s
        self.steps += 1
        while self.queue:
            uid, n = self.queue.pop(0)
            self.live[uid] = [0, n]
        out = []
        for uid, st in list(self.live.items()):
            out.append(TokenEvent(uid, 7, st[0], st[0] + 1 == st[1], 0))
            st[0] += 1
            if st[0] == st[1]:
                del self.live[uid]
        return out


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def drive(stall_at=None, stall_s=0.0, seed=3):
    import bench.drive as drive_mod

    clock = Clock()
    sleep = drive_mod.time.sleep
    drive_mod.time.sleep = lambda s: setattr(clock, "t", clock.t + max(s, 0.0))
    try:
        mix = {"kind": "open_loop", "rate_per_s": 20.0, "lead_in_s": 0.0,
               "prompt_tokens": {"median": 20, "sigma": 0.1, "min": 10, "max": 30},
               "output_tokens": {"median": 4, "sigma": 0.1, "min": 3, "max": 6}}
        reqs = traffic_gen.generate(mix, seed=seed, seconds=5.0, vocab_size=50)
        eng = FakeEngine(clock, stall_at=stall_at, stall_s=stall_s)
        d = Client(eng, reqs, common.traffic_kind("open_loop"), 4, clock=clock)
        d.start(clock())
        t0 = clock()
        d.run(t0 + 5.0)
        return d, t0
    finally:
        drive_mod.time.sleep = sleep


def test_arrivals_are_timed_from_the_schedule_not_from_submit():
    d, t0 = drive()
    for r in d.by_index:
        assert abs(r.due - (t0 + d.requests[r.index].due_s)) < 1e-12
    # a request due while a tick ran is submitted after it, but its clock
    # started at its due time: the wait shows in its time to first token
    late = [r for r in d.by_index if r.times and r.times[0] - r.due > 0.01 + 1e-9]
    assert late


def test_a_stall_in_the_window_moves_ttft_p95():
    calm, t0 = drive()
    stalled, s0 = drive(stall_at=100, stall_s=1.5)
    p_calm = e2e.p95(e2e.ttft(calm.by_index, t0, t0 + 5.0)[0])
    p_stall = e2e.p95(e2e.ttft(stalled.by_index, s0, s0 + 5.0)[0])
    assert p_stall > p_calm + 0.5


def test_generator_repeats_for_a_seed_and_keeps_the_schedule_across_seeds():
    mix = {"kind": "open_loop", "rate_per_s": 4.0, "lead_in_s": 6.0,
           "prompt_tokens": {"median": 384, "sigma": 0.6, "min": 128, "max": 1024},
           "output_tokens": {"median": 96, "sigma": 0.6, "min": 32, "max": 256}}
    a = traffic_gen.generate(mix, seed=(1 << 31) + 7, seconds=40, vocab_size=49152)
    b = traffic_gen.generate(mix, seed=(1 << 31) + 7, seconds=40, vocab_size=49152)
    c = traffic_gen.generate(mix, seed=12, seconds=40, vocab_size=49152)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    # another seed: the same sizes at the same moments, other token ids
    assert [r.due_s for r in a] == [r.due_s for r in c]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in c]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(128 <= len(r.prompt) <= 1024 for r in a)
    assert len(a) == 4 * 46 + 1
    assert abs(np.mean(np.diff([r.due_s for r in a])) - 0.25) < 0.01
    # a longer window appends requests and keeps the sizes of the first ones
    d = traffic_gen.generate(mix, seed=12, seconds=50, vocab_size=49152)
    assert [len(r.prompt) for r in d[: len(c)]] == [len(r.prompt) for r in c]


def test_backlog_is_all_due_at_once_with_unrounded_lengths():
    mix = {"kind": "backlog", "requests": 300,
           "prompt_tokens": {"median": 448, "sigma": 0.6, "min": 128, "max": 1024},
           "output_tokens": {"median": 256, "sigma": 0.5, "min": 128, "max": 512}}
    reqs = traffic_gen.generate(mix, seed=5, seconds=40, vocab_size=100)
    assert len(reqs) == 300 and all(r.due_s == 0.0 for r in reqs)
    lengths = [len(r.prompt) for r in reqs]
    assert len(set(lengths)) > 150 and min(lengths) >= 128 and max(lengths) <= 1024


def test_backlog_client_keeps_the_queue_at_the_slot_count():
    clock = Clock()
    mix = {"kind": "backlog", "requests": 40,
           "prompt_tokens": {"median": 20, "sigma": 0.1, "min": 10, "max": 30},
           "output_tokens": {"median": 5, "sigma": 0.3, "min": 3, "max": 8}}
    kind = common.traffic_kind("backlog")
    eng = FakeEngine(clock)
    d = Client(eng, traffic_gen.generate(mix, seed=1, seconds=5.0, vocab_size=50),
               kind, 4, clock=clock)
    d.start(clock())
    kind.lead_in(d, mix)  # opens once every slot decodes
    assert d.steps[-1].decode_slots == 4
    d.run(clock() + 0.2)
    assert d.next == 40 and all(r.finished for r in d.by_index[:20])
