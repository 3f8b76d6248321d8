"""The scale-out router's core, as units: HRW affinity stability
under replica add/remove, the power-of-two fallback ladder, the
health/backpressure state machine, /metrics aggregation semantics,
the failover-once rule, byte-identical stream passthrough, and the
``router_forward`` fault seam (submit + mid-stream).

Replicas here are FAKE — tiny apps on the framework's own server over
real sockets — so every routing/forwarding path runs against real
HTTP without an engine in sight (the 2-replica spawned-engine e2e
lives in ``test_router_e2e.py``). The router imports no jax; neither
do these tests' hot paths.
"""

import asyncio
import json

import httpx
import pytest

from mlapi_tpu.serving import faults
from mlapi_tpu.serving.asgi import (
    App,
    Request,
    StreamingResponse,
    json_response,
)
from mlapi_tpu.serving.router import (
    DOWN,
    DRAINING,
    LIVE,
    NoReplicaAvailable,
    ReplicaState,
    Router,
    _SubmitError,
    build_router_app,
    hrw_order,
)
from mlapi_tpu.serving.server import Server

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# HRW (rendezvous) hashing: the affinity map's stability contract.
# ---------------------------------------------------------------------------

KEYS = [f"prefix-{i}".encode() for i in range(240)]


def test_hrw_remove_remaps_only_the_removed_slice():
    names = ["h:1", "h:2", "h:3"]
    before = {k: hrw_order(k, names)[0] for k in KEYS}
    after = {k: hrw_order(k, ["h:1", "h:2"])[0] for k in KEYS}
    moved = [k for k in KEYS if before[k] != after[k]]
    # EVERY key that moved was on the removed replica; no key between
    # the survivors was touched — the property that lets one replica
    # drain without invalidating its peers' warm caches.
    assert moved, "no keys mapped to the removed replica at all?"
    assert all(before[k] == "h:3" for k in moved)
    assert all(after[k] == before[k] for k in KEYS if before[k] != "h:3")


def test_hrw_add_steals_only_for_the_new_replica():
    names = ["h:1", "h:2", "h:3"]
    before = {k: hrw_order(k, names)[0] for k in KEYS}
    after = {k: hrw_order(k, names + ["h:4"])[0] for k in KEYS}
    moved = [k for k in KEYS if before[k] != after[k]]
    assert moved, "a fourth replica should win some keys"
    assert all(after[k] == "h:4" for k in moved)


def test_hrw_spreads_keys_across_replicas():
    names = ["h:1", "h:2", "h:3"]
    counts = {n: 0 for n in names}
    for k in KEYS:
        counts[hrw_order(k, names)[0]] += 1
    # Loose balance bound: a uniform 64-bit hash puts each replica
    # within a comfortable margin of 1/3 over 240 keys.
    assert all(c >= len(KEYS) * 0.15 for c in counts.values()), counts


def test_hrw_is_deterministic_across_list_order():
    assert hrw_order(b"k", ["a:1", "b:2", "c:3"]) == hrw_order(
        b"k", ["c:3", "a:1", "b:2"]
    )


# ---------------------------------------------------------------------------
# choose(): affinity, the fallback ladder, round_robin, shedding.
# ---------------------------------------------------------------------------


def _router(n=3, **kw) -> Router:
    return Router([("127.0.0.1", 9000 + i) for i in range(n)], **kw)


def _preferred(router: Router, key: bytes) -> ReplicaState:
    order = hrw_order(key, [r.name for r in router.replicas])
    return next(r for r in router.replicas if r.name == order[0])


def test_affinity_routes_to_hrw_preferred():
    router = _router()
    key = b"system prompt abc"
    for _ in range(5):
        assert router.choose(key) is _preferred(router, key)
    assert router.affinity_hits == 5
    assert router.affinity_fallbacks == 0


def test_fallback_is_less_loaded_of_two_when_preferred_down():
    router = _router(3)
    key = b"some prefix"
    pref = _preferred(router, key)
    pref.state = DOWN
    others = [r for r in router.replicas if r is not pref]
    others[0].queue_depth = 100
    others[1].queue_depth = 0
    # p2c over exactly 2 routable replicas always samples both; the
    # less-loaded one must win every time.
    for _ in range(8):
        assert router.choose(key) is others[1]
    assert router.affinity_fallbacks == 8


def test_draining_preferred_falls_back_without_remapping_others():
    router = _router(3)
    keys = [f"k{i}".encode() for i in range(60)]
    before = {k: _preferred(router, k) for k in keys}
    victim = router.replicas[0]
    victim.state = DRAINING
    for k in keys:
        chosen = router.choose(k)
        if before[k] is not victim:
            # Unaffected slice: the drain of replica 0 must not move
            # these (their caches stay warm).
            assert chosen is before[k]
        else:
            assert chosen is not victim
    assert router.affinity_fallbacks == sum(
        1 for k in keys if before[k] is victim
    )


def test_queue_depth_limit_gates_routing():
    router = _router(2, queue_depth_limit=4)
    key = b"pfx"
    pref = _preferred(router, key)
    pref.queue_depth = 5
    assert router.choose(key) is not pref
    pref.queue_depth = 3
    assert router.choose(key) is pref


def test_round_robin_policy_cycles():
    router = _router(3, policy="round_robin")
    seen = [router.choose(b"same-key").name for _ in range(6)]
    assert seen[:3] == seen[3:6]
    assert len(set(seen[:3])) == 3
    assert router.affinity_hits == 0  # the A/B baseline never affines


def test_no_routable_replica_raises_with_retry_hint():
    router = _router(2)
    for r in router.replicas:
        r.state = DOWN
    with pytest.raises(NoReplicaAvailable):
        router.choose(b"k")


def test_shed_window_expires():
    import time as _time

    router = _router(2)
    key = b"pfx"
    pref = _preferred(router, key)
    pref.shed_until = _time.monotonic() + 30.0
    assert router.choose(key) is not pref
    pref.shed_until = 0.0
    assert router.choose(key) is pref


def _plain_request(headers=None) -> Request:
    return Request(
        {
            "method": "POST",
            "path": "/generate",
            "headers": list(headers or []),
        },
        b"{}",
    )


async def test_warm_peer_hint_is_hrw_head_on_every_non_preferred_forward():
    """The warmth contract (r17): a forward that misses the key's
    HRW-preferred replica carries that head as the warm-peer hint —
    and a forward that LANDS on the head carries none (nobody is
    warmer than the target itself)."""
    router = _router(3)
    key = b"some prefix"
    pref = _preferred(router, key)
    seen = []

    async def fake_attempt(r, request, warm_peer=None, extra=None):
        seen.append((r, warm_peer))
        return json_response({}, 200)

    router._attempt = fake_attempt
    await router.forward(_plain_request(), key)
    assert seen[-1] == (pref, None)
    assert router.warm_peer_hints == 0
    # Preferred draining: the fallback forward names the head.
    pref.state = DRAINING
    await router.forward(_plain_request(), key)
    target, hint = seen[-1]
    assert target is not pref and hint is pref
    assert router.warm_peer_hints == 1
    # No key (and round_robin policy): no warmth map, no hint.
    await router.forward(_plain_request(), None)
    assert seen[-1][1] is None
    rr = _router(3, policy="round_robin")
    rr._attempt = fake_attempt
    await rr.forward(_plain_request(), key)
    assert seen[-1][1] is None and rr.warm_peer_hints == 0


async def test_warm_peer_hint_survives_failover_hop():
    """The affinity-map blind spot (satellite fix): the failover's
    second choose() excludes the failed replica and re-ranks the rest
    — it has no memory of the ORIGINAL preferred. The hint must ride
    the original HRW head through the retry hop anyway."""
    router = _router(3)
    key = b"some prefix"
    pref = _preferred(router, key)
    calls = []

    async def fake_attempt(r, request, warm_peer=None, extra=None):
        calls.append((r, warm_peer))
        if len(calls) == 1:
            raise _SubmitError("injected pre-submit", retryable=True)
        return json_response({}, 200)

    router._attempt = fake_attempt
    await router.forward(_plain_request(), key)
    (first, hint1), (second, hint2) = calls
    assert first is pref and hint1 is None
    assert second is not pref
    assert hint2 is pref          # the head survived the retry hop
    assert router.failovers == 1
    assert router.warm_peer_hints == 1


def test_build_upstream_stamps_and_strips_warm_peer():
    """Anti-spoof parity with x-mlapi-router-depth: a client-sent
    warm-peer header is dropped (it could aim a replica's KV fetches
    at an arbitrary host), and the router-authored one appears
    exactly once, naming the hinted replica."""
    router = _router(2)
    target, peer = router.replicas
    req = _plain_request(
        headers=[
            (b"x-mlapi-warm-peer", b"evil.example:9"),
            (b"content-type", b"application/json"),
        ]
    )
    head = router._build_upstream(req, target, peer).split(
        b"\r\n\r\n"
    )[0].lower()
    assert head.count(b"x-mlapi-warm-peer") == 1
    assert b"x-mlapi-warm-peer: " + peer.name.encode() in head
    assert b"evil.example" not in head
    # No hint: the header is absent entirely.
    head2 = router._build_upstream(req, target, None).split(
        b"\r\n\r\n"
    )[0].lower()
    assert b"x-mlapi-warm-peer" not in head2


def test_role_pools_and_disagg_gate():
    """Role-split units (r18): wants_disagg fires only in a
    role-split fleet for plain prompt bodies (prefix-carrying and
    unparseable bodies stay on the affinity path); _pick_role picks
    inside one pool by HRW (key) or load (None) and returns None for
    a starved pool; an all-mixed router has no role surface at all —
    bit-identical to r17."""
    mixed = _router(3)
    assert not mixed.role_split
    assert not mixed.wants_disagg(json.dumps({"text": "hi"}).encode())

    router = Router(
        [("127.0.0.1", 9000 + i) for i in range(4)],
        roles=["prefill", "prefill", "decode", "decode"],
    )
    assert router.role_split
    assert router.wants_disagg(json.dumps({"text": "hi"}).encode())
    assert not router.wants_disagg(
        json.dumps({"text": "hi", "prefix": "sys"}).encode()
    )
    assert not router.wants_disagg(b"not json")
    assert not router.wants_disagg(json.dumps({"text": ""}).encode())

    key = b"some prompt"
    dec = router._pick_role(key, "decode")
    assert dec is not None and dec.role == "decode"
    # HRW stability: same key, same decode pick, every time.
    assert router._pick_role(key, "decode") is dec
    pre = router._pick_role(None, "prefill")
    assert pre is not None and pre.role == "prefill"
    # A starved pool returns None (the forward degrades to mixed
    # routing, counted) — never a member of the other pool.
    for r in router.replicas:
        if r.role == "prefill":
            r.state = DOWN
    assert router._pick_role(None, "prefill") is None
    assert router._pick_role(key, "decode") is not None

    # Role validation is loud.
    with pytest.raises(ValueError):
        Router([("h", 1)], roles=["imaginary"])
    with pytest.raises(ValueError):
        Router([("h", 1), ("h", 2)], roles=["mixed"])


def test_build_upstream_stamps_and_strips_disagg_headers():
    """The r18 headers ride the same anti-spoof contract as
    warm-peer: client-sent copies are stripped (they could aim a
    replica's pushes at an arbitrary host or claim a foreign
    transfer), router-authored extras appear exactly once."""
    router = _router(2)
    target = router.replicas[0]
    req = _plain_request(
        headers=[
            (b"x-mlapi-decode-peer", b"evil.example:9"),
            (b"x-mlapi-kv-xfer", b"stolen"),
        ]
    )
    head = router._build_upstream(
        req, target, None,
        {"x-mlapi-decode-peer": "10.0.0.2:8001", "x-mlapi-kv-xfer": "xf1"},
    ).split(b"\r\n\r\n")[0].lower()
    assert head.count(b"x-mlapi-decode-peer") == 1
    assert b"x-mlapi-decode-peer: 10.0.0.2:8001" in head
    assert head.count(b"x-mlapi-kv-xfer") == 1
    assert b"x-mlapi-kv-xfer: xf1" in head
    assert b"evil.example" not in head and b"stolen" not in head
    # No extras: both headers absent entirely.
    head2 = router._build_upstream(req, target, None).split(
        b"\r\n\r\n"
    )[0].lower()
    assert b"x-mlapi-decode-peer" not in head2
    assert b"x-mlapi-kv-xfer" not in head2


def test_routing_key_prefers_prefix_field_and_truncates():
    router = _router(2, affinity_prefix_bytes=8)
    body = json.dumps(
        {"text": "completely different", "prefix": "shared-system-prompt"}
    ).encode()
    assert router.routing_key(body) == b"shared-s"
    assert router.routing_key(json.dumps({"text": "hello"}).encode()) == (
        b"hello"
    )
    assert router.routing_key(b"not json") is None
    assert router.routing_key(json.dumps({"stream": True}).encode()) is None


# ---------------------------------------------------------------------------
# Fake replicas over real sockets: polling, forwarding, faults.
# ---------------------------------------------------------------------------


def make_replica(name: str, state: dict):
    """A fake replica speaking the real control+data surface: unary
    and streaming /generate (echoing which replica served), /healthz
    with the draining flag, /metrics with counters/gauges."""
    app = App(title=name)
    state.setdefault("requests", 0)
    state.setdefault("qd", 0)
    state.setdefault("counters", {})

    @app.post("/generate")
    async def generate(request):
        state["requests"] += 1
        body = json.loads(request.body)
        if state.get("shed"):
            return json_response(
                {"detail": "overloaded"}, 503,
                headers={"retry-after": str(state.get("retry_after", 2))},
            )
        if body.get("stream"):
            async def frames():
                for fr in state.get(
                    "frames",
                    [
                        {"token_ids": [1, 2], "replica": name},
                        {"done": True, "text": "hi", "replica": name},
                    ],
                ):
                    yield json.dumps(fr).encode() + b"\n"
                    if state.get("die_after_first_frame"):
                        raise ConnectionResetError("replica died")

            return StreamingResponse(
                frames(), content_type="application/x-ndjson"
            )
        return {"replica": name, "text": "hi"}

    @app.get("/healthz")
    async def healthz():
        return {
            "status": "draining" if state.get("draining") else "ok",
            "queue_depth": state["qd"],
        }

    @app.get("/metrics")
    async def metrics():
        return {
            "counters": dict(state["counters"]),
            "gauges": {"generate.queue_depth": state["qd"]},
        }

    return app


class _Fleet:
    def __init__(self):
        self.states: list[dict] = []
        self.servers: list[Server] = []

    async def add(self, name: str) -> dict:
        state: dict = {}
        srv = Server(make_replica(name, state), host="127.0.0.1", port=0)
        await srv.start()
        self.states.append(state)
        self.servers.append(srv)
        return state

    @property
    def endpoints(self):
        return [("127.0.0.1", s.port) for s in self.servers]

    async def stop(self):
        for s in self.servers:
            await s.stop()


@pytest.fixture
async def fleet():
    f = _Fleet()
    await f.add("A")
    await f.add("B")
    yield f
    await f.stop()


async def _client(router: Router):
    transport = httpx.ASGITransport(app=build_router_app(router))
    return httpx.AsyncClient(transport=transport, base_url="http://router")


async def test_health_poll_state_transitions(fleet):
    router = Router(fleet.endpoints, health_poll_s=0.05, assume_live=False)
    assert all(r.state == DOWN for r in router.replicas)
    await router.start()
    try:
        assert all(r.state == LIVE for r in router.replicas)
        fleet.states[0]["draining"] = True
        fleet.states[1]["qd"] = 7
        for _ in range(100):
            await asyncio.sleep(0.05)
            if (
                router.replicas[0].state == DRAINING
                and router.replicas[1].queue_depth == 7
            ):
                break
        assert router.replicas[0].state == DRAINING
        assert router.replicas[1].queue_depth == 7
        # Kill replica 0's listener: two failed polls mark it down.
        await fleet.servers[0].stop()
        for _ in range(200):
            await asyncio.sleep(0.05)
            if router.replicas[0].state == DOWN:
                break
        assert router.replicas[0].state == DOWN
        assert router.replicas[1].state == LIVE
    finally:
        await router.stop()


async def test_metrics_aggregation_sums_counters_labels_gauges(fleet):
    fleet.states[0]["counters"] = {"generate.requests": 3, "only.a": 1}
    fleet.states[1]["counters"] = {"generate.requests": 5}
    fleet.states[1]["qd"] = 9
    router = Router(fleet.endpoints)
    router.affinity_hits = 11
    snap = await router.metrics_snapshot()
    assert snap["counters"]["generate.requests"] == 8  # summed
    assert snap["counters"]["only.a"] == 1
    assert snap["counters"]["router.affinity_hits"] == 11
    b = router.replicas[1].name
    assert snap["gauges"][f"replica.{b}.generate.queue_depth"] == 9
    assert snap["gauges"][f"router.replica.{b}.queue_depth"] == 9
    assert snap["gauges"]["router.replicas_live"] == 2
    assert snap["replicas_stale"] == []


async def test_affinity_repeats_land_on_one_replica(fleet):
    router = Router(fleet.endpoints)
    async with await _client(router) as c:
        served = set()
        for _ in range(4):
            r = await c.post(
                "/generate", json={"text": "same shared prompt here"}
            )
            assert r.status_code == 200
            served.add(r.json()["replica"])
    assert len(served) == 1
    assert router.affinity_hits == 4
    # Exactly one fake replica saw all four requests.
    assert sorted(s["requests"] for s in fleet.states) == [0, 4]


async def test_stream_relay_is_byte_identical(fleet):
    fleet.states[0]["frames"] = fleet.states[1]["frames"] = [
        {"token_ids": [5, 6, 7]},
        {"token_ids": [8]},
        {"done": True, "text": "xy", "token_ids": [5, 6, 7, 8]},
    ]
    router = Router(fleet.endpoints)
    payload = {"text": "stream me", "stream": True}
    # Direct to the replica affinity picks, then through the router.
    key = router.routing_key(json.dumps(payload).encode())
    pref = router.choose(key)
    async with httpx.AsyncClient() as direct:
        d = await direct.post(
            f"http://{pref.name}/generate", json=payload
        )
    async with await _client(router) as c:
        v = await c.post("/generate", json=payload)
    assert v.status_code == d.status_code == 200
    assert v.content == d.content  # byte-for-byte, terminal frame included
    assert v.headers["content-type"] == d.headers["content-type"]


async def test_failover_once_on_dead_replica(fleet):
    # Point one endpoint at a dead port: connect refused is the
    # provably-not-submitted failure — exactly one failover hop.
    dead_port = fleet.servers[0].port
    await fleet.servers[0].stop()
    router = Router(
        [("127.0.0.1", dead_port), ("127.0.0.1", fleet.servers[1].port)]
    )
    async with await _client(router) as c:
        responses = [
            await c.post("/generate", json={"text": f"p{i}"})
            for i in range(6)
        ]
    assert all(r.status_code == 200 for r in responses)
    assert all(r.json()["replica"] == "B" for r in responses)
    # The dead replica was marked down on first contact, so at most
    # the keys that preferred it cost a failover — and only until the
    # state flipped (no polling here; the forward path marked it).
    assert router.replicas[0].state == DOWN
    assert 1 <= router.failovers <= 6
    assert fleet.states[1]["requests"] == 6


async def test_replica_503_sheds_and_fails_over_with_no_duplicate(fleet):
    key_text = "shed-me shed-me"
    router = Router(fleet.endpoints)
    pref = router.choose(router.routing_key(
        json.dumps({"text": key_text}).encode()
    ))
    shed_state = fleet.states[0 if pref.name.endswith(
        str(fleet.servers[0].port)) else 1]
    other_state = fleet.states[1] if shed_state is fleet.states[0] else (
        fleet.states[0]
    )
    shed_state["shed"] = True
    async with await _client(router) as c:
        r = await c.post("/generate", json={"text": key_text})
    assert r.status_code == 200
    assert router.failovers == 1
    # The shedding replica answered exactly once (the 503) — the
    # failover hop did not resubmit there.
    assert shed_state["requests"] == 1
    assert other_state["requests"] == 1
    # And its shed window is open: the next same-key request skips it
    # without costing another 503 round trip.
    async with await _client(router) as c:
        r2 = await c.post("/generate", json={"text": key_text})
    assert r2.status_code == 200
    assert shed_state["requests"] == 1
    assert router.failovers == 1  # fallback, not failover, this time


async def test_all_replicas_shedding_relays_503_with_retry_after(fleet):
    for s in fleet.states:
        s["shed"] = True
        s["retry_after"] = 3
    router = Router(fleet.endpoints)
    async with await _client(router) as c:
        r = await c.post("/generate", json={"text": "anything"})
    assert r.status_code == 503
    assert "retry-after" in r.headers
    # The second hop's 503 is the REPLICA's response, relayed.
    assert r.json() == {"detail": "overloaded"}


async def test_all_replicas_down_sheds_at_router_door():
    router = Router([("127.0.0.1", 1), ("127.0.0.1", 2)])
    for r in router.replicas:
        r.state = DOWN
    async with await _client(router) as c:
        r = await c.post("/generate", json={"text": "x"})
    assert r.status_code == 503
    assert r.headers.get("retry-after")
    assert router.shed_no_replica == 1


async def test_router_forward_fault_at_submit_single_failover(fleet):
    """The fault-matrix submit leg: a raise BEFORE the first request
    byte triggers exactly one failover hop and no duplicate submit —
    the faulted replica never sees the request at all."""
    router = Router(fleet.endpoints)
    with faults.active("router_forward:raise"):  # one shot, first call
        async with await _client(router) as c:
            r = await c.post("/generate", json={"text": "fault me"})
        assert r.status_code == 200
    assert router.failovers == 1
    # ONE replica served it; the fleet saw exactly one request total.
    assert sum(s["requests"] for s in fleet.states) == 1
    assert faults.injected_count() == 0  # disarmed resets the counter


async def test_router_forward_fault_midstream_terminal_frame(fleet):
    """The fault-matrix mid-stream leg: a raise while relaying yields
    a WELL-FORMED error terminal frame — parseable NDJSON with a
    code — never a truncated stream, and never a retry."""
    router = Router(fleet.endpoints)
    # after=1 skips the submit-seam fire; the relay of the first
    # chunk is call 2 and raises.
    with faults.active("router_forward:after=1:raise"):
        async with await _client(router) as c:
            r = await c.post(
                "/generate", json={"text": "stream", "stream": True}
            )
            assert r.status_code == 200
            lines = r.content.decode().strip().splitlines()
    frames = [json.loads(ln) for ln in lines]  # every line parses
    assert frames[-1]["code"] == "upstream_error"
    assert "error" in frames[-1]
    assert router.failovers == 0  # never mid-stream
    assert router.stream_upstream_errors == 1
    # Fresh work flows afterward (the conservation half: the router
    # state machine survived the injected failure).
    async with await _client(router) as c:
        ok = await c.post("/generate", json={"text": "after the fault"})
    assert ok.status_code == 200


async def test_router_forward_delay_slows_never_breaks(fleet):
    """The fault-matrix delay leg: a delay at the seam slows the
    relay (submit and every chunk) but every stream still completes
    byte-complete with its real terminal frame."""
    router = Router(fleet.endpoints)
    with faults.active("router_forward:delay=0.01"):
        async with await _client(router) as c:
            r = await c.post(
                "/generate", json={"text": "slowly", "stream": True}
            )
            assert r.status_code == 200
            frames = [
                json.loads(ln)
                for ln in r.content.decode().strip().splitlines()
            ]
        assert frames[-1]["done"] is True  # real terminal frame
        assert faults.injected_count() >= 2  # submit + chunks all fired
    assert router.failovers == 0
    assert router.stream_upstream_errors == 0


async def test_upstream_death_midstream_appends_error_frame():
    """Not injected — a RAW replica that tears the TCP stream after
    one chunk (no terminal 0-chunk, socket just closes): the relayed
    frame survives, the router appends its well-formed error terminal
    frame, and the client never sees a truncated line."""
    frame1 = json.dumps({"token_ids": [1, 2]}).encode() + b"\n"

    async def torn_replica(reader, writer):
        await reader.readuntil(b"\r\n\r\n")
        body = (
            b"HTTP/1.1 200 OK\r\ncontent-type: application/x-ndjson\r\n"
            b"transfer-encoding: chunked\r\nconnection: close\r\n\r\n"
            + b"%x\r\n" % len(frame1) + frame1 + b"\r\n"
        )
        writer.write(body)
        await writer.drain()
        writer.close()  # mid-stream death: no terminal chunk

    srv = await asyncio.start_server(torn_replica, "127.0.0.1", 0)
    port = srv.sockets[0].getsockname()[1]
    try:
        router = Router([("127.0.0.1", port)])
        async with await _client(router) as c:
            r = await c.post(
                "/generate", json={"text": "doomed", "stream": True}
            )
            lines = r.content.decode().strip().splitlines()
        frames = [json.loads(ln) for ln in lines]
        assert frames[0]["token_ids"] == [1, 2]  # the relayed real frame
        assert frames[-1]["code"] == "upstream_error"
        assert router.stream_upstream_errors == 1
        assert router.failovers == 0  # never mid-stream
    finally:
        srv.close()
        await srv.wait_closed()


async def test_predict_routes_by_load_and_healthz_reports(fleet):
    """/predict has no prefix economics: it spreads by p2c. The
    router-level /healthz reports per-replica state for the layer
    above."""
    router = Router(fleet.endpoints, health_poll_s=0.05)

    # Fake /predict on the replicas (the fake app only has /generate).
    for srv, st in zip(fleet.servers, fleet.states):
        app = srv.app

        @app.post("/predict")
        async def predict(request, _st=st):
            _st["requests"] += 1
            return {"prediction": "x", "probability": 0.5}

    async with await _client(router) as c:
        for _ in range(10):
            r = await c.post("/predict", json={"features": [1.0]})
            assert r.status_code == 200
        h = (await c.get("/healthz")).json()
    assert h["router"] is True
    assert h["replicas_live"] == 2
    assert {rep["state"] for rep in h["replicas"]} == {"live"}
    # p2c over equal load spreads (seeded rng: both replicas serve).
    assert all(s["requests"] > 0 for s in fleet.states)


async def test_router_healthz_degraded_when_fleet_down(fleet):
    router = Router(fleet.endpoints)
    for r in router.replicas:
        r.state = DOWN
    h = router.health_snapshot()
    assert h["status"] == "degraded"
    assert h["replicas_down"] == 2
