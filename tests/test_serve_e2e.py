"""End-to-end serving tests: concurrent sessions through the in-process
client (and the real HTTP endpoint) must produce greedy output
token-identical to a direct `models/generate.py` call with the same
params/prompt — the ISSUE acceptance path — plus loadgen smoke.

One module-scoped server (started once, stopped at teardown) backs every
test except the deliberately-tiny backpressure stack and the CLI selftest
(which builds its own model through the real command path) — so the file
pays each XLA compile once."""

import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, make_generate_fn
from lstm_tensorspark_tpu.serve import (
    InprocessClient,
    ServeEngine,
    ServeServer,
    run_loadgen,
)

_CFG = LMConfig(vocab_size=41, hidden_size=16, num_layers=2)
_N_NEW = 8
_PROMPTS = [
    np.array([7, 1], np.int32),
    np.array([3, 9, 2, 12, 30], np.int32),
]


@pytest.fixture(scope="module")
def stack():
    params = init_lm(jax.random.PRNGKey(7), _CFG)
    engine = ServeEngine(
        params, _CFG, num_slots=8,
        prefill_buckets=(4, 8), batch_buckets=(1, 2, 4),
    )
    server = ServeServer(engine, max_active=4, queue_size=16)
    server.start()
    yield params, server
    server.stop()


@pytest.fixture(scope="module")
def refs(stack):
    """Greedy references for _PROMPTS, one compiled program per prompt
    length, computed once for the whole file."""
    params, _ = stack
    gen = make_generate_fn(_CFG, max_new_tokens=_N_NEW, greedy=True)
    return [
        np.asarray(gen(params, p[None, :], jax.random.PRNGKey(0)))[0, p.size:]
        for p in _PROMPTS
    ]


def test_concurrent_inprocess_sessions_match_generate(stack, refs):
    _, server = stack
    client = InprocessClient(server)
    got = [None] * len(_PROMPTS)

    def run_one(i):
        got[i] = client.generate(_PROMPTS[i], max_new_tokens=_N_NEW)

    threads = [threading.Thread(target=run_one, args=(i,))
               for i in range(len(_PROMPTS))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i in range(len(_PROMPTS)):
        np.testing.assert_array_equal(np.asarray(got[i], np.int32), refs[i])


def test_http_endpoint_roundtrip(stack, refs):
    from lstm_tensorspark_tpu.serve.server import make_http_server

    _, server = stack
    httpd = make_http_server(server, port=0)
    host, port = httpd.server_address[:2]
    http_thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    try:
        http_thread.start()
        base = f"http://{host}:{port}"
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
            # honest-health contract (test_serve_health.py): ok + the
            # scheduler heartbeat, not a constant smile
            assert health["ok"] is True and health["batcher_alive"] is True
        body = json.dumps({
            "prompt": _PROMPTS[1].tolist(), "max_new_tokens": _N_NEW,
            "greedy": True,
        }).encode()
        req = urllib.request.Request(
            base + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        with urllib.request.urlopen(base + "/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
    finally:
        httpd.shutdown()
        httpd.server_close()
    np.testing.assert_array_equal(np.asarray(out["tokens"], np.int32), refs[1])
    assert stats["batcher"]["completed"] >= 1


@pytest.mark.parametrize("mode,rc", [
    (["--selftest"], 0),
    # speed is measured in one place (BENCHMARK.json + benchmark/): the
    # CLI has no load test of its own, and none comes back through a comma
    (["--loadgen"], 2),
    (["--selftest", "--replicas", "1,2"], 2),
    (["--selftest", "--decode-kernel", "pallas,scan"], 2),
], ids=["selftest", "no_loadgen", "no_replica_list", "no_kernel_list"])
def test_cli_serve_selftest(mode, rc):
    """The acceptance command: `cli serve --selftest` exits 0 (PASS);
    what is not a mode or a value of `cli serve` is a usage error."""
    from lstm_tensorspark_tpu.cli import main

    argv = [
        "serve", *mode, "--vocab-size", "31", "--hidden-units", "12",
        "--num-layers", "1", "--sessions", "2", "--max-new-tokens", "4",
        "--prefill-buckets", "8", "--batch-buckets", "2",
    ]
    if rc == 0:
        assert main(argv) == 0
        return
    with pytest.raises(SystemExit) as usage:
        main(argv)
    assert usage.value.code == rc


def test_loadgen_reports_latency_and_throughput(stack):
    _, server = stack
    report = run_loadgen(
        server, vocab_size=_CFG.vocab_size, sessions=2,
        requests_per_session=2, prompt_len=4, max_new_tokens=4,
    )
    assert report["completed"] == 4 and report["rejected"] == 0
    assert report["failed"] == 0
    assert report["tokens_generated"] == 16
    for key in ("p50_latency_ms", "p99_latency_ms", "p50_ttft_ms",
                "tokens_per_sec"):
        assert report[key] > 0, (key, report)
    assert report["p99_latency_ms"] >= report["p50_latency_ms"]
    # inter-token latency is reported SEPARATELY from end-to-end latency
    # (the decode-window K tradeoff must be visible, not inferred): every
    # request contributes tokens-1 gaps, and a gap can't exceed the
    # request's own latency. ITL can be exactly 0.0 — a decode window's
    # K tokens arrive in one burst and share a timestamp — so assert
    # presence/ordering, not positivity.
    for key in ("p50_itl_ms", "p99_itl_ms", "max_itl_ms"):
        assert report[key] >= 0 and np.isfinite(report[key]), (key, report)
    assert report["p99_itl_ms"] >= report["p50_itl_ms"]
    assert report["max_itl_ms"] > 0
    assert report["max_itl_ms"] <= report["p99_latency_ms"]


def test_loadgen_open_loop_counts_backpressure():
    """Open-loop arrivals against a tiny queue: the run completes and every
    request is either completed or counted rejected (429-equivalent)."""
    params = init_lm(jax.random.PRNGKey(7), _CFG)
    engine = ServeEngine(params, _CFG, num_slots=2,
                         prefill_buckets=(4,), batch_buckets=(1,))
    server = ServeServer(engine, max_active=1, queue_size=1)
    with server:
        report = run_loadgen(
            server, vocab_size=_CFG.vocab_size, sessions=4,
            requests_per_session=2, prompt_len=3, max_new_tokens=3,
            mode="open", rate=200.0,
        )
    assert report["completed"] + report["rejected"] == 8
    assert report["completed"] >= 1
