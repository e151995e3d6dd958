"""The generator: the seed decides everything, every seed offers the same
work, latency counts from the due time, lateness is reported."""
import collections
import json
import os
import time

import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))


def traffic():
    with open(os.path.join(HERE, "..", "traffic", "serve-steady-0.8knee.json")) as f:
        return json.load(f)


def key(a):
    return (a.due, a.prompt_len, a.new_tokens, a.resident)


def test_same_seed_same_schedule_and_words():
    t = traffic()
    one, two = (loadgen.make_schedule(t, 2_500_000_123, 12.0, preroll_s=5.0) for _ in range(2))
    assert [key(a) for a in one] == [key(a) for a in two]
    assert (loadgen.words(one[3], 50_000, 9) == loadgen.words(two[3], 50_000, 9)).all()
    other = loadgen.make_schedule(t, 7, 12.0, preroll_s=5.0)
    assert [key(a) for a in one] != [key(a) for a in other]


def test_every_seed_offers_the_same_work():
    t = traffic()
    a, b = (loadgen.make_schedule(t, s, 12.0, preroll_s=5.0) for s in (1, 2))
    n_pre, n_win = round(t["rate_per_s"] * 5.0), round(t["rate_per_s"] * 12.0)
    assert len(a) == len(b) == n_pre + n_win
    for part in (lambda s: [x for x in s if x.due < 0],
                 lambda s: [x for x in s if 0 <= x.due < 12.0]):
        assert len(part(a)) == len(part(b)) in (n_pre, n_win)
        for field in ("prompt_len", "new_tokens"):     # the same work in the window
            assert sorted(getattr(x, field) for x in part(a)) == \
                sorted(getattr(x, field) for x in part(b))
    # the same gaps too: each seed leaves a different LAST gap unseen
    gaps = lambda s: collections.Counter(  # noqa: E731
        round(y.due - x.due, 9) for x, y in zip(s, s[1:]))
    assert sum(((gaps(a[n_pre:]) - gaps(b[n_pre:]))).values()) <= 1
    assert a[0].due == -5.0 and a[n_pre].due == 0.0
    p = t["prompt_len"]
    assert all(p["min"] <= x.prompt_len <= p["max"] for x in a)


def test_continuations_name_distinct_resident_sessions():
    t = traffic()
    s = loadgen.make_schedule(t, 5, 20.0, preroll_s=5.0)
    turns = [a.resident for a in s if a.resident is not None]
    assert len(turns) == round(t["continue_share"] * round(t["rate_per_s"] * 5.0)) \
        + round(t["continue_share"] * round(t["rate_per_s"] * 20.0))
    assert len(set(turns)) == len(turns)          # a session is continued once
    assert all(0 <= r < t["resident_sessions"] for r in turns)


def test_latency_counts_from_the_due_time_and_lateness_is_reported():
    """A server that stalls 50 ms per request behind ONE client thread: the
    second request leaves late, and its wait is in the number."""
    t = {"rate_per_s": 100.0, "continue_share": 0.0, "resident_sessions": 0,
         "prompt_len": {"median": 4, "sigma": 0.1, "min": 4, "max": 4},
         "output_len": {"median": 4, "sigma": 0.1, "min": 4, "max": 4}}
    arrivals = loadgen.make_schedule(t, 0, 0.03, preroll_s=0.0)   # 3 within 30 ms
    assert arrivals[0].due == 0.0 and arrivals[2].due < 0.03

    def send(o):
        time.sleep(0.05)
        o.first_token_at = o.done_at = time.perf_counter()
        o.ok = True

    loop = loadgen.OpenLoop(arrivals, send, workers=1)
    opens = time.perf_counter() + 0.02
    out = loop.run(opens, drain_s=5.0)
    assert all(o.ok for o in out)
    from_due = [o.first_token_at - o.due_at for o in out]
    assert from_due[0] >= 0.05 and from_due[1] >= 0.07 and from_due[2] >= 0.12
    assert out[0].late_s < 0.02 and out[2].late_s >= 0.07    # and it says so
    assert loadgen.in_flight(out, opens + 0.06) == 2
