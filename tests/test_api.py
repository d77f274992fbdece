"""The ISSUE-19 front door, fast tier: multi-tenant scheduling policy
units (weighted fair share, priority admission/preemption, shed-vs-defer)
over the REAL Scheduler + BlockKVCache, golden fixtures for the API's
parsing/error/SSE surfaces, and a real-socket ApiServer driven against a
duck-typed fake engine (no jax compiles, no subprocesses) covering
streaming framing, auth, rejection, shed 429, and the no-hang deadline
backstop.  The engine-parity half (streamed tokens == generate()) lives
in the serve_smoke --api leg; the chaos half (stall + mid-stream kill)
in scripts/api_smoke.py (slow tier, run at the bottom of this file).
"""
import itertools
import json
import os
import pathlib
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.monitor import slo as mslo
from paddle_tpu.monitor import wire
from paddle_tpu.serving import BlockKVCache, Request, SamplingParams, Scheduler
from paddle_tpu.serving.api import ApiServer, api_error, parse_api_keys
from paddle_tpu.serving.scheduler import (PRIORITIES, priority_rank,
                                          should_shed, tenant_weights,
                                          worst_fast_burn)


# -- multi-tenant scheduling policy (real Scheduler, no engine) --------------

def _cache(num_blocks=64):
    return BlockKVCache(num_layers=1, num_blocks=num_blocks, block_size=4,
                        num_heads=1, head_dim=2)


def _req(rid, tenant=None, priority="interactive", prompt_len=4,
         max_new_tokens=4):
    return Request(rid, list(range(1, prompt_len + 1)), SamplingParams(
        max_new_tokens=max_new_tokens, tenant=tenant, priority=priority))


def _drive_saturated(weights, tenants, rounds=400):
    """A tiny engine loop in pure Python: both tenants keep two requests
    queued (saturation), max_num_seqs=1 so every admission is contended,
    each request prefills 4 tokens then decodes to max_new_tokens.
    Returns generated-token counts per tenant."""
    sched = Scheduler(_cache(), max_num_seqs=1, weights=weights)
    counts = {t: 0 for t in tenants}
    nid = itertools.count()
    for _ in range(rounds):
        for t in tenants:   # top up: saturating offered load per tenant
            backlog = sum(1 for r in list(sched.waiting) + sched.running
                          if r.params.tenant == t)
            for _ in range(2 - backlog):
                sched.add(_req(f"{t}-{next(nid)}", tenant=t))
        out = sched.schedule()
        if out.kind == "prefill":
            r = out.prefill_request
            r.num_computed += out.chunk_len
            if r.prefill_done:   # the engine samples token 1 off prefill
                r.record_token(7)
        elif out.kind == "decode":
            for r in out.decode_requests:
                r.record_token(7)
        for r in sched.retire_finished():
            counts[r.params.tenant] += len(r.output_ids)
    return counts


class TestFairShare:
    def test_weighted_split_within_10_percent(self):
        # two saturating tenants at weights 3:1 -> served tokens split
        # 3:1 (the ISSUE-19 acceptance bound: within 10%)
        counts = _drive_saturated({"acme": 3.0, "free": 1.0},
                                  ("acme", "free"))
        assert counts["free"] > 0, counts
        ratio = counts["acme"] / counts["free"]
        assert abs(ratio - 3.0) / 3.0 <= 0.10, counts

    def test_equal_weights_split_evenly(self):
        counts = _drive_saturated({}, ("a", "b"))   # unlisted = weight 1
        assert counts["b"] > 0, counts
        ratio = counts["a"] / counts["b"]
        assert abs(ratio - 1.0) <= 0.10, counts

    def test_default_params_degenerate_to_fifo(self):
        # no tenants, one priority: admission must be exact arrival order
        sched = Scheduler(_cache(), max_num_seqs=4)
        for i in range(3):
            sched.add(_req(f"r{i}"))
        admitted = []
        for _ in range(3):
            out = sched.schedule()
            assert out.kind == "prefill"
            out.prefill_request.num_computed = out.prefill_request.prompt_len
            admitted.append(out.prefill_request.req_id)
        assert admitted == ["r0", "r1", "r2"]

    def test_late_joiner_starts_at_current_minimum(self):
        # a tenant arriving after incumbents built up service history
        # must NOT monopolize admission until it "catches up" from zero
        sched = Scheduler(_cache(), max_num_seqs=1, weights={})
        sched.tenant_served = {"a": 40.0, "b": 50.0}
        assert sched._served_of("newcomer") == 40.0
        sched._charge(_req("n1", tenant="newcomer"), 4)
        assert sched.tenant_served["newcomer"] == 44.0


class TestPriority:
    def test_admission_prefers_higher_class_over_arrival(self):
        # best-effort arrived FIRST; interactive must still go first —
        # then fair share/arrival break ties within a class
        sched = Scheduler(_cache(), max_num_seqs=4)
        sched.add(_req("be", priority="best-effort"))
        sched.add(_req("batch", priority="batch"))
        sched.add(_req("int", priority="interactive"))
        order = []
        for _ in range(3):
            out = sched.schedule()
            assert out.kind == "prefill"
            out.prefill_request.num_computed = out.prefill_request.prompt_len
            order.append(out.prefill_request.req_id)
        assert order == ["int", "batch", "be"]

    def test_preemption_victimizes_lowest_priority_youngest(self):
        sched = Scheduler(_cache(), max_num_seqs=4)
        rows = [_req("int-old", priority="interactive"),
                _req("be-old", priority="best-effort"),
                _req("be-young", priority="best-effort"),
                _req("batch", priority="batch")]
        for i, r in enumerate(rows):
            r.arrival = i
            r.state = Request.RUNNING
        sched.running = list(rows)
        assert sched._pick_victim().req_id == "be-young"
        assert sched._pick_victim(exclude=rows[2]).req_id == "be-old"
        # one class in play: the original youngest-arrival pick
        sched.running = [rows[0], _req("int-young")]
        sched.running[1].arrival = 9
        assert sched._pick_victim().req_id == "int-young"

    def test_unknown_priority_ranks_worst(self):
        assert priority_rank("interactive") == 0
        assert priority_rank("batch") == 1
        assert priority_rank("best-effort") == len(PRIORITIES) - 1
        assert priority_rank("totally-bogus") == priority_rank("best-effort")
        assert priority_rank(None) == priority_rank("best-effort")


class TestShedPolicy:
    @pytest.mark.parametrize("priority,burn,expect", [
        ("interactive", 10.0, False),    # never shed: defers in queue
        ("batch", 10.0, False),          # never shed: defers in queue
        ("best-effort", 10.0, True),     # burn >= threshold: shed
        ("best-effort", 1.9, False),     # below the 2.0 default: defer
        ("best-effort", 2.0, True),      # threshold is inclusive
        ("bogus", 10.0, True),           # unknown class degrades to BE
        (None, 10.0, True),
    ])
    def test_shed_vs_defer_matrix(self, priority, burn, expect):
        assert should_shed(priority, burn=burn) is expect

    def test_threshold_env_override(self, monkeypatch):
        monkeypatch.setenv("PTPU_SHED_BURN", "5.0")
        assert not should_shed("best-effort", burn=4.9)
        assert should_shed("best-effort", burn=5.0)
        monkeypatch.setenv("PTPU_SHED_BURN", "not-a-number")
        assert should_shed("best-effort", burn=2.0)   # falls back to 2.0

    def test_worst_fast_burn_reads_report(self):
        rep = {"enabled": True, "objectives": [
            {"burn_rate": {"fast": 1.5, "slow": 0.2}},
            {"burn_rate": {"fast": 3.25, "slow": 0.1}},
        ]}
        assert worst_fast_burn(rep) == 3.25
        assert worst_fast_burn({"enabled": False, "objectives": []}) == 0.0
        assert worst_fast_burn({}) == 0.0

    def test_tenant_weights_parsing(self):
        assert tenant_weights("acme:3,free:1") == {"acme": 3.0, "free": 1.0}
        assert tenant_weights("solo") == {"solo": 1.0}
        # malformed / non-positive entries are dropped, never fatal
        assert tenant_weights("bad:x, ok:2 ,:3,neg:-1,zero:0") == {"ok": 2.0}
        assert tenant_weights("") == {}


# -- API parsing / error-shape golden fixtures -------------------------------

class TestApiFixtures:
    def test_parse_api_keys(self):
        assert parse_api_keys(
            "sk-a:acme:interactive,sk-b:free:best-effort") == {
                "sk-a": ("acme", "interactive"),
                "sk-b": ("free", "best-effort")}
        assert parse_api_keys("sk-a") == {"sk-a": (None, None)}
        assert parse_api_keys("sk-a:t") == {"sk-a": ("t", None)}
        assert parse_api_keys(" sk-a:t:p , ,:orphan") == {
            "sk-a": ("t", "p")}
        assert parse_api_keys("") == {}

    def test_api_error_matches_wire_schema(self):
        doc = api_error("boom", code="shed", param="prompt")
        assert set(doc) == {"error"}
        assert tuple(doc["error"].keys()) == wire.API_ERROR_KEYS
        assert doc["error"]["message"] == "boom"
        assert doc["error"]["code"] == "shed"
        assert api_error("x")["error"]["type"] == "invalid_request_error"

    def test_shed_and_rejected_are_slo_good(self):
        from paddle_tpu.monitor.slo import _GOOD_REASONS

        assert "shed" in _GOOD_REASONS and "rejected" in _GOOD_REASONS
        # and the reqlog wire schema carries the tenant dimension
        assert "tenant" in wire.REQLOG_EVENT_KEYS
        assert "priority" in wire.REQLOG_EVENT_KEYS


# -- the HTTP tier over a duck-typed fake engine -----------------------------

class _FakeReq:
    def __init__(self, prompt_ids, params):
        self.prompt_ids = list(prompt_ids)
        self.params = params
        self.output_ids = []
        self.finish_reason = None


class _FakeEngine:
    """The LLMEngine half the pump drives, deterministic and compile-free:
    one token per step (last prompt id + position), finishing at
    max_new_tokens/eos.  `wedged=True` never produces tokens — the
    backstop-timer case."""

    def __init__(self, wedged=False):
        self._requests = {}
        self._next = itertools.count()
        self.released = []
        self.wedged = wedged

    def add_request(self, prompt_ids, params):
        if not prompt_ids:
            raise ValueError("empty prompt")
        rid = next(self._next)
        self._requests[rid] = _FakeReq(prompt_ids, params)
        return rid

    def has_unfinished(self):
        return any(r.finish_reason is None for r in self._requests.values())

    def step(self):
        if self.wedged:
            time.sleep(0.005)
            return
        for r in self._requests.values():
            if r.finish_reason is not None:
                continue
            tok = (r.prompt_ids[-1] + len(r.output_ids) + 1) % 50000
            r.output_ids.append(tok)
            p = r.params
            if len(r.output_ids) >= p.max_new_tokens or (
                    p.eos_token_id is not None and tok == p.eos_token_id):
                r.finish_reason = "stop"

    def release_request(self, rid, reason=None):
        self.released.append((rid, reason))
        self._requests.pop(rid, None)


class _BurnStub:
    """Duck-typed monitor.slo engine: the full contract the serving stack
    touches is report() + violates() + tick()."""

    def __init__(self, fast):
        self.fast = fast

    def report(self):
        return {"enabled": True, "objectives": [
            {"objective": "stub", "burn_rate": {"fast": self.fast,
                                                "slow": 0.0}}]}

    def violates(self, **kw):
        return False

    def tick(self, now=None):
        return None


def _post(url, body, key=None, timeout=30):
    headers = {"Content-Type": "application/json"}
    if key:
        headers["Authorization"] = "Bearer " + key
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers=headers)
    return urllib.request.urlopen(req, timeout=timeout)


def _sse_chunks(resp):
    """Parse a full SSE body into its JSON chunks, asserting the exact
    framing: every event is one `data: <json>` line + blank line, and
    the terminator is `data: [DONE]`."""
    raw = resp.read().decode("utf-8")
    events = [e for e in raw.split("\n\n") if e]
    assert all(e.startswith("data: ") for e in events), raw
    assert events[-1] == "data: [DONE]", raw
    return [json.loads(e[len("data: "):]) for e in events[:-1]]


@pytest.fixture()
def server():
    eng = _FakeEngine()
    srv = ApiServer(engine=eng, api_keys={}, poll_s=0.005)
    try:
        yield srv, eng
    finally:
        srv.stop()


class TestApiServer:
    def test_a_batch_of_callers_connecting_at_once_is_queued(self):
        """64 closed-loop callers reconnect together whenever a step ends
        their requests.  With `socketserver`'s accept queue of 5, those
        the accept loop had not taken yet were reset at their first write
        (`ConnectionResetError`, 3 of ~550 requests on the chip, PERF.md
        PR 32).  Here nobody accepts at all: every connection of a burst
        of 200 must still complete its handshake from the queue."""
        import socket

        from paddle_tpu.serving.api import _ApiHandler, _Httpd

        httpd = _Httpd(("127.0.0.1", 0), _ApiHandler)   # never served
        conns = []
        try:
            assert httpd.request_queue_size >= 1024 and httpd.daemon_threads
            for _ in range(200):
                c = socket.create_connection(httpd.server_address[:2],
                                             timeout=5)
                c.sendall(b"POST /v1/completions HTTP/1.1\r\n")
                conns.append(c)
        finally:
            for c in conns:
                c.close()
            httpd.server_close()
        assert len(conns) == 200

    def test_models_endpoint(self, server):
        srv, _ = server
        doc = json.loads(urllib.request.urlopen(
            srv.url + "/v1/models", timeout=10).read())
        assert doc["data"][0]["id"] == "paddle-tpu"

    def test_completion_json(self, server):
        srv, _ = server
        doc = json.loads(_post(srv.url + "/v1/completions",
                               {"prompt": [5, 6, 7],
                                "max_tokens": 4}).read())
        assert doc["object"] == "text_completion"
        ch = doc["choices"][0]
        assert ch["token_ids"] == [8, 9, 10, 11]   # fake's arithmetic
        assert ch["finish_reason"] == "stop"
        assert ch["text"] == " 8 9 10 11"          # default decode
        assert doc["usage"] == {"prompt_tokens": 3, "completion_tokens": 4,
                                "total_tokens": 7}

    def test_completion_stream_framing(self, server):
        srv, eng = server
        chunks = _sse_chunks(_post(srv.url + "/v1/completions",
                                   {"prompt": [5, 6, 7], "max_tokens": 4,
                                    "stream": True}))
        # one chunk per pump cycle (= one fake token) + the final chunk
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert toks == [8, 9, 10, 11], chunks
        assert all(c["object"] == "text_completion" for c in chunks)
        assert len({c["id"] for c in chunks}) == 1   # stable stream id
        reasons = [c["choices"][0]["finish_reason"] for c in chunks]
        assert reasons[-1] == "stop"
        assert all(r is None for r in reasons[:-1]), reasons
        assert chunks[-1]["choices"][0]["token_ids"] == []
        assert not eng._requests, "stream end must release the request"

    def test_chat_completion_json_and_stream(self, server):
        srv, _ = server
        body = {"messages": [{"role": "user", "content": [5, 6, 7]}],
                "max_tokens": 3}
        doc = json.loads(_post(srv.url + "/v1/chat/completions",
                               body).read())
        assert doc["object"] == "chat.completion"
        msg = doc["choices"][0]["message"]
        assert msg["role"] == "assistant" and msg["content"] == " 8 9 10"
        chunks = _sse_chunks(_post(srv.url + "/v1/chat/completions",
                                   dict(body, stream=True)))
        assert chunks[0]["object"] == "chat.completion.chunk"
        assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert toks == [8, 9, 10]

    def test_eos_stops_early(self, server):
        srv, _ = server
        doc = json.loads(_post(srv.url + "/v1/completions",
                               {"prompt": [5, 6, 7], "max_tokens": 16,
                                "eos_token_id": 9}).read())
        assert doc["choices"][0]["token_ids"] == [8, 9]

    def test_bad_requests_are_400_with_wire_shape(self, server):
        srv, _ = server
        for body in ({"prompt": "strings need a tokenizer"},
                     {"prompt": []}, {"prompt": {"not": "a list"}},
                     {"messages": []}):
            path = ("/v1/chat/completions" if "messages" in body
                    else "/v1/completions")
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(srv.url + path, body)
            assert ei.value.code == 400
            err = json.loads(ei.value.read())["error"]
            assert tuple(err.keys()) == wire.API_ERROR_KEYS

    def test_unknown_model_404(self, server):
        srv, _ = server
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/v1/completions",
                  {"model": "gpt-oss-999", "prompt": [1]})
        assert ei.value.code == 404
        assert json.loads(ei.value.read())["error"]["code"] == \
            "model_not_found"

    def test_auth_401_and_tenant_mapping(self):
        eng = _FakeEngine()
        srv = ApiServer(engine=eng, poll_s=0.005,
                        api_keys={"sk-a": ("acme", "batch")})
        try:
            for key in (None, "sk-wrong"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post(srv.url + "/v1/completions", {"prompt": [1]},
                          key=key)
                assert ei.value.code == 401
                err = json.loads(ei.value.read())["error"]
                assert err["type"] == "authentication_error"
                assert err["code"] == "invalid_api_key"
            _post(srv.url + "/v1/completions",
                  {"prompt": [1], "max_tokens": 1}, key="sk-a").read()
            (rid, reason), = eng.released
            assert reason is None   # finished normally, key accepted
            # the key's (tenant, priority) landed on SamplingParams; the
            # body can override priority but not the key's tenant
            st = _post(srv.url + "/v1/completions",
                       {"prompt": [1], "max_tokens": 1, "user": "spoof",
                        "priority": "interactive"}, key="sk-a")
            st.read()
        finally:
            srv.stop()

    def test_shed_429_via_slo_stub(self):
        eng = _FakeEngine()
        srv = ApiServer(engine=eng, poll_s=0.005,
                        api_keys={"sk-be": ("free", "best-effort"),
                                  "sk-int": ("acme", "interactive")})
        mslo.install(_BurnStub(fast=10.0))
        try:
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(srv.url + "/v1/completions",
                      {"prompt": [1], "max_tokens": 1}, key="sk-be")
            bounded = time.monotonic() - t0
            assert ei.value.code == 429
            assert ei.value.headers.get("Retry-After")
            assert json.loads(ei.value.read())["error"]["code"] == "shed"
            assert bounded < 5.0, "shed must answer immediately"
            assert not eng._requests, "shed work must never reach the queue"
            # interactive under the SAME burn: served, not shed
            doc = json.loads(_post(srv.url + "/v1/completions",
                                   {"prompt": [1], "max_tokens": 1},
                                   key="sk-int").read())
            assert doc["choices"][0]["finish_reason"] == "stop"
            # burn below threshold: best-effort is served again
            mslo.install(_BurnStub(fast=0.5))
            doc = json.loads(_post(srv.url + "/v1/completions",
                                   {"prompt": [1], "max_tokens": 1},
                                   key="sk-be").read())
            assert doc["choices"][0]["finish_reason"] == "stop"
        finally:
            mslo.refresh()
            srv.stop()

    def test_deadline_backstop_never_hangs(self, monkeypatch):
        # a wedged backend (steps but never produces): the HTTP tier's
        # deadline+grace budget must answer 504, bounded, both modes
        from paddle_tpu.serving import api as api_mod

        monkeypatch.setattr(api_mod, "_DEADLINE_GRACE_S", 0.3)
        eng = _FakeEngine(wedged=True)
        srv = ApiServer(engine=eng, api_keys={}, poll_s=0.005)
        try:
            for stream in (False, True):
                t0 = time.monotonic()
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post(srv.url + "/v1/completions",
                          {"prompt": [1], "max_tokens": 4,
                           "deadline_s": 0.2, "stream": stream})
                dt = time.monotonic() - t0
                assert ei.value.code == 504
                assert json.loads(ei.value.read())["error"]["code"] == \
                    "deadline"
                assert dt < 3.0, f"stream={stream} hung {dt:.1f}s"
            # the pump releases cancelled requests on its next cycle
            deadline = time.monotonic() + 5.0
            while eng._requests and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not eng._requests, "timed-out requests must be released"
        finally:
            srv.stop()

    def test_backend_exception_surfaces_as_500(self, server):
        srv, eng = server

        def boom():
            raise RuntimeError("backend on fire")

        eng.step = boom
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url + "/v1/completions",
                  {"prompt": [1], "max_tokens": 2})
        assert ei.value.code == 500
        err = json.loads(ei.value.read())["error"]
        assert err["type"] == "api_error"
        assert "backend on fire" in err["message"]


# -- the chaos half: scripts/api_smoke.py (slow tier) ------------------------

@pytest.mark.slow
def test_api_smoke_script():
    """Stall + mid-stream SIGKILL behind the API: every HTTP stream
    completes, errors cleanly, or fails over — never hangs."""
    script = (pathlib.Path(__file__).resolve().parent.parent
              / "scripts" / "api_smoke.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS", "PTPU_FAULTS")}
    env["PTPU_FORCE_PLATFORM"] = "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    env["PTPU_MONITOR"] = "1"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "API SMOKE OK" in proc.stdout
    assert "stall leg:" in proc.stdout
    assert "failover leg:" in proc.stdout
