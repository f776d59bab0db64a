"""End-to-end front-end tests over a REAL localhost socket: the
asyncio HTTP/1.1 + SSE server, hand-rolled client included. The
acceptance scenario: N concurrent SSE streams, one cancelled
mid-stream via DELETE, one expiring its deadline in the queue — every
timeline completes, invariants hold, no slot leaks."""

import asyncio
import json

import numpy as np
import pytest

from deepspeed_tpu.serving import (FinishReason, ServingEngine,
                                   ServingFrontend)

# compile time lands in the first TTFT; keep burn shedding out of the
# basic e2e flows (the shed path is asserted separately with the SLO
# tracker driven directly)
LENIENT_SLO = {"ttft_ms": 6e5, "gap_ms": 6e5}


# ---------------------------------------------------------------------------
# minimal HTTP/SSE client (stdlib asyncio streams, like the server)
# ---------------------------------------------------------------------------
def _http_bytes(method, path, body=None):
    payload = b"" if body is None else json.dumps(body).encode("utf-8")
    head = (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n")
    return head.encode("latin-1") + payload


async def _request(port, method, path, body=None):
    """One full request/response exchange; returns (status, headers,
    body bytes). Relies on the server's Connection: close framing."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(_http_bytes(method, path, body))
    await writer.drain()
    raw = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionResetError, BrokenPipeError):
        pass
    head, _, payload = raw.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, payload


async def _read_sse_head(reader):
    """Consume the HTTP response head of an SSE stream; returns status."""
    head = await reader.readuntil(b"\r\n\r\n")
    return int(head.decode("latin-1").split("\r\n")[0].split(" ")[1])


async def _next_frame(reader):
    """Parse one ``event:``/``data:`` SSE frame, or None on EOF."""
    try:
        block = await reader.readuntil(b"\n\n")
    except asyncio.IncompleteReadError:
        return None
    event, data = None, None
    for line in block.decode("utf-8").strip().split("\n"):
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            data = json.loads(line[len("data: "):])
    return event, data


async def _generate(port, payload):
    """POST /v1/generate and read frames to completion. Returns the
    frame list (or the error JSON dict on a non-200 response)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(_http_bytes("POST", "/v1/generate", payload))
    await writer.drain()
    status = await _read_sse_head(reader)
    if status != 200:
        body = await reader.read()
        writer.close()
        await writer.wait_closed()
        return status, json.loads(body) if body else {}
    frames = []
    while True:
        fr = await _next_frame(reader)
        if fr is None:
            break
        frames.append(fr)
        if fr[0] in ("done", "error"):
            break
    writer.close()
    await writer.wait_closed()
    return status, frames


def _frontend(stack, **srv_kw):
    _, _, engine = stack
    srv_kw.setdefault("num_slots", 2)
    srv = ServingEngine(engine, **srv_kw)
    return srv, ServingFrontend(srv, port=0, idle_poll_s=0.005)


def _assert_clean(srv):
    srv.check_invariants()
    assert srv.pool.free_count == srv.pool.num_slots
    assert srv.live_count == 0
    assert srv.timelines.open_ids() == []


# ---------------------------------------------------------------------------
class TestHTTP:
    def test_acceptance_concurrent_cancel_and_deadline(self, stack):
        """The ISSUE's e2e acceptance: concurrent SSE streams + one
        mid-stream DELETE + one queued deadline expiry, all timelines
        complete over a real socket."""
        srv, fe = _frontend(stack, num_slots=2, priority=True,
                            slo=LENIENT_SLO)

        async def run():
            await fe.start()
            port = fe.port
            try:
                # warm the compiled programs so stream timing is sane
                await _generate(port, {"prompt": [1, 2, 3],
                                       "max_new_tokens": 2})

                async def normal(i):
                    return await _generate(port, {
                        "prompt": [1 + i, 2, 3], "max_new_tokens": 4 + i,
                        "priority": "interactive", "tenant": f"t{i}"})

                async def cancelled():
                    reader, writer = await asyncio.open_connection(
                        "127.0.0.1", port)
                    writer.write(_http_bytes("POST", "/v1/generate", {
                        "prompt": [9, 9, 9], "max_new_tokens": 48}))
                    await writer.drain()
                    assert await _read_sse_head(reader) == 200
                    ev, data = await _next_frame(reader)
                    assert ev == "start"
                    rid = data["request_id"]
                    # one token through, then DELETE on a 2nd connection
                    await _next_frame(reader)
                    st, _, body = await _request(
                        port, "DELETE", f"/v1/requests/{rid}")
                    assert st == 200
                    frames = []
                    while True:
                        fr = await _next_frame(reader)
                        if fr is None:
                            break
                        frames.append(fr)
                        if fr[0] in ("done", "error"):
                            break
                    writer.close()
                    await writer.wait_closed()
                    return rid, frames

                async def expiring():
                    # both slots busy with the load above; 30 ms is far
                    # less than the queue wait behind 48-token decodes
                    return await _generate(port, {
                        "prompt": [5, 5, 5], "max_new_tokens": 4,
                        "deadline_ms": 30.0, "priority": "batch"})

                results = await asyncio.gather(
                    cancelled(), expiring(),
                    *[normal(i) for i in range(5)])
            finally:
                await fe.stop()
            return results

        (cancel_rid, cancel_frames), (exp_status, exp_frames), *normals = \
            asyncio.run(run())
        # 5 normal streams: start -> tokens (monotone indices) -> done
        for st, frames in normals:
            assert st == 200
            assert frames[0][0] == "start"
            toks = [d for e, d in frames if e == "token"]
            assert [t["index"] for t in toks] == list(range(len(toks)))
            assert frames[-1][0] == "done"
            assert frames[-1][1]["reason"] in ("eos", "length")
        # the DELETEd stream terminates with done/cancelled
        assert cancel_frames[-1][0] == "done"
        assert cancel_frames[-1][1]["reason"] == "cancelled"
        # the queued request expired without ever costing a slot
        assert exp_status == 200
        assert exp_frames[-1][0] == "done"
        assert exp_frames[-1][1]["reason"] == "deadline"
        _assert_clean(srv)
        tl = [e["event"] for e in srv.timeline(cancel_rid)]
        assert tl[-1] == "finished"

    def test_healthz_and_metrics(self, stack):
        srv, fe = _frontend(stack, priority=True, slo=LENIENT_SLO)

        async def run():
            await fe.start()
            try:
                h = await _request(fe.port, "GET", "/healthz")
                m = await _request(fe.port, "GET", "/metrics")
            finally:
                await fe.stop()
            return h, m

        (hst, _, hbody), (mst, mhdr, mbody) = asyncio.run(run())
        assert hst == 200
        info = json.loads(hbody)
        assert info["state"] == "healthy"
        assert info["num_slots"] == 2 and info["live_slots"] == 0
        assert set(info["class_queue_depths"]) == {"interactive",
                                                   "standard", "batch"}
        assert "class_alerts" in info and "goodput" in info
        assert mst == 200
        assert mhdr["content-type"].startswith("text/plain")
        assert b"# TYPE" in mbody or b"# HELP" in mbody

    def test_rejection_maps_to_http_error_before_stream(self, stack):
        srv, fe = _frontend(
            stack, num_slots=1, max_queue_depth=1,
            priority={"tenants": {"slow": {"tokens_per_s": 1.0,
                                           "burst_tokens": 8.0}}})

        async def run():
            await fe.start()
            port = fe.port
            try:
                # rate limit: burst 8 < prompt 3 + budget 8
                st1, body1 = await _generate(port, {
                    "prompt": [1, 2, 3], "max_new_tokens": 8,
                    "tenant": "slow"})
                # prompt too long: can never fit capacity
                st2, body2 = await _generate(port, {
                    "prompt": [1] * 60, "max_new_tokens": 32})
            finally:
                await fe.stop()
            return (st1, body1), (st2, body2)

        (st1, body1), (st2, body2) = asyncio.run(run())
        assert st1 == 429 and body1["reject_reason"] == "rate_limited"
        assert body1["retry_after_s"] > 0
        assert st2 == 400 and body2["reject_reason"] == "prompt_too_long"
        _assert_clean(srv)

    def test_client_disconnect_mid_stream_cancels_request(self, stack):
        srv, fe = _frontend(stack)

        async def run():
            await fe.start()
            port = fe.port
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                writer.write(_http_bytes("POST", "/v1/generate", {
                    "prompt": [1, 2, 3], "max_new_tokens": 48}))
                await writer.drain()
                assert await _read_sse_head(reader) == 200
                ev, data = await _next_frame(reader)
                rid = data["request_id"]
                await _next_frame(reader)        # one token flowing
                writer.transport.abort()         # RST: client vanishes
                # the server notices on its next write and cancels
                for _ in range(400):
                    await asyncio.sleep(0.005)
                    done = await fe.bridge.call(
                        lambda s: s.live_count == 0
                        and s.scheduler.pending == 0)
                    if done:
                        break
            finally:
                await fe.stop()
            return rid

        rid = asyncio.run(run())
        _assert_clean(srv)
        events = srv.timeline(rid)
        assert events[-1]["event"] == "finished"
        assert events[-1]["attrs"]["reason"] == "cancelled"

    def test_malformed_requests(self, stack):
        srv, fe = _frontend(stack)

        async def run():
            await fe.start()
            port = fe.port
            try:
                results = {
                    "no_route": await _request(port, "GET", "/nope"),
                    "bad_method": await _request(port, "GET",
                                                 "/v1/generate"),
                    "bad_json": await _request(port, "POST", "/v1/generate",
                                               body=None),
                    "bad_prompt": await _request(port, "POST",
                                                 "/v1/generate",
                                                 {"prompt": "hi"}),
                    "unknown_field": await _request(
                        port, "POST", "/v1/generate",
                        {"prompt": [1], "stream": True}),
                    "bad_cancel_id": await _request(
                        port, "DELETE", "/v1/requests/xyz"),
                    "unknown_cancel": await _request(
                        port, "DELETE", "/v1/requests/424242"),
                }
            finally:
                await fe.stop()
            return results

        r = asyncio.run(run())
        assert r["no_route"][0] == 404
        assert r["bad_method"][0] == 405
        assert r["bad_json"][0] == 400
        assert r["bad_prompt"][0] == 400
        assert r["unknown_field"][0] == 400
        assert json.loads(r["unknown_field"][2])["error"].count("stream")
        assert r["bad_cancel_id"][0] == 400
        assert r["unknown_cancel"][0] == 404
        _assert_clean(srv)

    def test_zero_recompiles_after_warmup_across_http_load(self, stack):
        """The whole HTTP/bridge/priority stack must not perturb the
        engine's compiled surface: warm up, then drive mixed-class load
        over the socket and require zero post-warmup recompiles."""
        srv, fe = _frontend(stack, num_slots=2, priority=True,
                            slo=LENIENT_SLO)

        async def run():
            await fe.start()
            port = fe.port
            try:
                for i in range(3):       # warmup sweep over the buckets
                    await _generate(port, {"prompt": [1 + i, 2, 3],
                                           "max_new_tokens": 3})
                await fe.bridge.call(lambda s: s.end_warmup())
                await asyncio.gather(*[
                    _generate(port, {
                        "prompt": [i + 1, 3, 5], "max_new_tokens": 3 + i,
                        "priority": ("interactive", "standard",
                                     "batch")[i % 3]})
                    for i in range(6)])
                return await fe.bridge.call(
                    lambda s: s.watchdog.recompiles)
            finally:
                await fe.stop()

        assert asyncio.run(run()) == 0
        _assert_clean(srv)


# ---------------------------------------------------------------------------
class TestFleetFrontend:
    def test_healthz_fleet_topology_and_router_metrics(self, stack):
        """The frontend over a DISAGGREGATED fleet: ``/healthz`` carries
        the fleet block (per-role counts, transfers in flight, last
        scale event) and ``/metrics`` the router gauges — with one
        generation riding a real cross-pool page transfer end to end
        over the socket."""
        from deepspeed_tpu.serving.router import ReplicaRouter

        _, _, engine = stack

        def rep(role):
            return ServingEngine(
                engine, num_slots=2, max_queue_depth=32, prefill_chunk=8,
                paged_kv={"page_size": 8, "num_pages": None}, role=role)

        router = ReplicaRouter([rep("prefill"), rep("decode")])
        fe = ServingFrontend(router, port=0, idle_poll_s=0.005)

        async def run():
            await fe.start()
            try:
                st, frames = await _generate(fe.port, {
                    "prompt": list(range(1, 13)), "max_new_tokens": 4})
                h = await _request(fe.port, "GET", "/healthz")
                m = await _request(fe.port, "GET", "/metrics")
            finally:
                await fe.stop()
            return st, frames, h, m

        st, frames, (hst, _, hbody), (mst, mhdr, mbody) = asyncio.run(run())
        assert st == 200 and frames[0][0] == "start"
        assert frames[-1][0] == "done"
        assert len([f for f in frames if f[0] == "token"]) == 4
        assert hst == 200
        info = json.loads(hbody)
        assert info["state"] in ("healthy", "pressured")
        assert info["num_slots"] == 4 and info["live_slots"] == 0
        fleet = info["fleet"]
        assert fleet["counts"] == {"prefill": 1, "decode": 1, "both": 0}
        assert fleet["fleet_size"] == 2
        assert fleet["transfers_in_flight"] == 0
        assert fleet["transfers_total"] >= 1
        assert "last_scale_event" in fleet
        assert mst == 200
        assert mhdr["content-type"].startswith("text/plain")
        text = mbody.decode("utf-8")
        assert "router_fleet_size 2" in text
        assert "router_transfers_total" in text
        # fleet observability plane: /healthz carries the per-replica /
        # per-role summary, /metrics the merged labeled exposition
        fh = info["fleet_health"]
        assert set(fh["replicas"]) == {"0", "1"}
        assert fh["replicas"]["0"]["role"] == "prefill"
        assert set(fh["roles"]) == {"prefill", "decode"}
        assert fh["journeys"]["complete"] == fh["journeys"]["finished"]
        assert 'replica="0",role="prefill"' in text
        assert "fleet_goodput" in text
        assert "fleet_journeys_complete" in text
        router.check_invariants()
