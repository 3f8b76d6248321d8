"""Scale-out serving: an asyncio front-end router over N engine replicas.

One engine process is now dense with capability (paged pool r09,
flash extend r11, robustness r12, host-RAM tier r13) but it is still
ONE process; the next axis is *out* (ROADMAP item 3). This module is
the front end for a fleet of full engine replicas — separate
processes, each serving the whole r13 stack on its own port — that
spreads ``/generate``, ``/predict``, and streaming NDJSON traffic
over them while keeping each replica's caches hot.

Why the replica choice is the whole game: at millions-of-users scale
prefix reuse is the dominant cache economics (ROADMAP item 2), and a
prefix's pool pages and kv_tier blobs live in ONE replica's memory.
A load balancer that sprays requests uniformly makes every replica
rebuild every prefix — N replicas, ~N× the cold prefills, and the r13
host tier goes cold. So the routing policy is **prefix-hash affinity
with a power-of-two-choices fallback**:

- The router tokenizes nothing. It takes the request's routing key —
  the ``prefix`` field when present (that is the shared-prompt cache
  unit), else the prompt text — truncated to the first K BYTES
  (``affinity_prefix_bytes``, CLI ``--affinity-prefix-bytes``), and
  ranks replicas by **rendezvous (highest-random-weight) hashing**.
  HRW's property is exactly the scale-out story: adding or removing
  one replica remaps ONLY the keys that preferred it — every other
  replica's affinity slice (and therefore its warm pages, tier blobs,
  and compiled shapes) is untouched.
- When the preferred replica is not routable — shedding (a recent
  503/retry-after), draining (its ``/healthz`` says so — poll-cached
  per replica), down (failed polls / refused connects), or over the
  queue-depth threshold scraped from its ``/metrics`` — the router
  falls back to the **less loaded of two random routable replicas**
  (power of two choices: near-optimal load spread at O(1) state,
  without the herding a deterministic second choice causes).

Failure semantics (the part a proxy one-liner gets wrong):

- **Failover-once, never mid-stream.** A submit that provably never
  reached a replica (connect refused, the ``router_forward`` fault
  seam firing before the first request byte is written) or that the
  replica REFUSED whole (a 503 — sheds happen at the replica's door,
  before any decode work) retries exactly one hop on a
  power-of-two-chosen alternate. Once request bytes are on the wire
  with no response, or once any response byte has been relayed, there
  is no retry — a duplicate generation is worse than an honest 502.
- **Streams end in terminal frames, always.** The NDJSON passthrough
  relays body bytes verbatim (the replica's ``DeadlineExceeded`` /
  ``DrainCancelled`` terminal frames reach the client byte-for-byte);
  if the upstream dies mid-stream the router appends a well-formed
  ``{"error": ..., "code": "upstream_error"}`` frame — never a
  truncated stream.

Warmth hinting (r17, ``serving/kv_peer.py``): affinity is a
PREFERENCE, not a placement constraint — any forward that misses the
key's HRW head (p2c fallback, failover, depth overflow, post-drain
remap) carries ``x-mlapi-warm-peer: host:port`` naming that head, so
a ``--kv-peer-fetch`` replica can pull the prefix KV from where it is
warm instead of re-prefilling. The head is computed once per request
over ALL replicas and threaded through the failover hop too (the
second ``choose()`` excludes the failed replica and would otherwise
forget who was preferred).

Observability: the router's ``/metrics`` sums replica counters (the
fleet-wide totals), labels per-replica gauges
(``replica.<host:port>.<gauge>``), and adds its own
``router.affinity_{hits,fallbacks}``, ``router.failovers``,
``router.replicas_{live,draining,down}`` and per-replica queue-depth
gauges; ``/healthz`` reports replica liveness for the layer above
(routers stack: a pod-level balancer health-checks this endpoint the
way this router health-checks its replicas).

The router deliberately imports no jax and touches no device: it is
pure asyncio and can front replicas on other hosts unchanged
(``--replica-urls`` / ``$MLAPI_TPU_REPLICAS`` — the env-driven
discovery mirror of ``parallel/distributed.py``'s rendezvous trio).
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import random
import time

from mlapi_tpu.serving import faults
from mlapi_tpu.serving.asgi import (
    App,
    Request,
    Response,
    StreamingResponse,
    json_response,
)
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.router")

DEFAULT_AFFINITY_PREFIX_BYTES = 64

# Replica lifecycle states (the health/backpressure state machine).
LIVE = "live"
DRAINING = "draining"
DOWN = "down"

# Hop-by-hop / framing headers never forwarded in either direction
# (RFC 9110 §7.6.1): the router re-frames each hop itself.
_HOP_HEADERS = frozenset(
    (
        b"host",
        b"connection",
        b"keep-alive",
        b"content-length",
        b"transfer-encoding",
        b"te",
        b"upgrade",
        b"expect",
        b"proxy-authorization",
        b"proxy-authenticate",
    )
)


class NoReplicaAvailable(Exception):
    """Every replica is down, draining, shedding, or over the queue
    threshold: the router sheds at ITS door (503 + retry-after), the
    same contract a single overloaded replica gives its clients."""

    def __init__(self, retry_after_s: float = 1.0):
        super().__init__("no live replica available")
        self.retry_after_s = retry_after_s


class _SubmitError(Exception):
    """One forward attempt failed. ``retryable`` says whether the
    failover hop is safe (the request provably never started work on
    the replica); ``response`` carries a complete replica response
    (e.g. its 503) to relay if no hop remains."""

    def __init__(self, detail: str, *, retryable: bool,
                 response: Response | None = None):
        super().__init__(detail)
        self.detail = detail
        self.retryable = retryable
        self.response = response


def hrw_weight(key: bytes, name: str) -> int:
    """The rendezvous weight of ``name`` for ``key``: a stable 64-bit
    digest (blake2b — NOT Python's ``hash``, which is per-process
    salted and would scatter affinity across router restarts)."""
    h = hashlib.blake2b(digest_size=8)
    h.update(name.encode())
    h.update(b"\x00")
    h.update(key)
    return int.from_bytes(h.digest(), "big")


def hrw_order(key: bytes, names: list[str]) -> list[str]:
    """Replica names ranked by rendezvous hash for ``key`` (highest
    weight first; name breaks the astronomically-unlikely tie so the
    order is total). The stability property routing leans on: removing
    a name never changes the relative order of the others, so only
    keys whose TOP choice vanished remap — each to its key-specific
    runner-up, spreading the lost slice over the fleet instead of
    shifting everyone (what modulo hashing would do)."""
    return sorted(names, key=lambda n: (-hrw_weight(key, n), n))


class ReplicaState:
    """One replica as the router sees it: its address plus the cached
    health/backpressure state the routing decision reads. Updated by
    the poll loop (``/healthz`` liveness, ``/metrics`` queue depth)
    and by forward outcomes (refused connects mark it down
    immediately; a 503 opens a shed window from its retry-after —
    faster feedback than the next poll tick)."""

    __slots__ = (
        "host", "port", "name", "state", "queue_depth", "inflight",
        "shed_until", "poll_failures", "last_poll", "healthz",
        "metrics", "role", "models",
    )

    def __init__(self, host: str, port: int, *, assume_live: bool = True,
                 role: str = "mixed"):
        self.host = host
        self.port = port
        self.name = f"{host}:{port}"
        # Disaggregation role (r18): "prefill" replicas take the
        # first hop of role-split generative traffic, "decode"
        # replicas own the streams; "mixed" (default) serves both —
        # an all-mixed fleet routes exactly as r17 did.
        self.role = role
        # assume_live=False (the CLI topology) gates routing on the
        # first successful health poll — a replica still booting its
        # engine never sees traffic; True is the embedded/unit default
        # where the caller controls replica lifetime itself.
        self.state = LIVE if assume_live else DOWN
        # Model ids this replica advertises on /healthz (r22 multi-
        # model fleets): None until a poll says otherwise — an
        # unpolled or single-model replica serves the default model
        # only, and the model filter treats it that way. The r18
        # role generalized: a fleet whose replicas advertise
        # different model sets IS the per-model replica-group
        # topology, discovered, not configured.
        self.models: frozenset | None = None
        self.queue_depth = 0
        self.inflight = 0        # router-side in-flight forwards
        self.shed_until = 0.0    # monotonic: shedding until then
        self.poll_failures = 0
        self.last_poll: float | None = None
        self.healthz: dict = {}
        self.metrics: dict = {}

    def routable(self, now: float, depth_limit: int | None) -> bool:
        if self.state != LIVE or now < self.shed_until:
            return False
        if depth_limit is not None and (
            self.queue_depth + self.inflight > depth_limit
        ):
            return False
        return True

    def load(self) -> int:
        """The power-of-two comparison key: the replica's own queue
        depth (from its last scrape) plus the router's in-flight
        forwards to it (fresher than any scrape)."""
        return self.queue_depth + self.inflight


async def _read_response_head(reader) -> tuple[int, dict[bytes, bytes]]:
    head = await reader.readuntil(b"\r\n\r\n")
    lines = head.split(b"\r\n")
    try:
        status = int(lines[0].split(b" ", 2)[1])
    except (IndexError, ValueError):
        raise ConnectionError(f"malformed upstream status line {lines[0]!r}")
    headers: dict[bytes, bytes] = {}
    for line in lines[1:]:
        if not line:
            continue
        k, sep, v = line.partition(b":")
        if sep:
            headers[k.strip().lower()] = v.strip()
    return status, headers


async def _iter_chunked(reader):
    """Decode an upstream chunked body incrementally — one yielded
    bytes object per upstream chunk, so relayed tokens reach the
    client with the same cadence the replica produced them."""
    while True:
        size_line = (await reader.readuntil(b"\r\n")).strip()
        size = int(size_line.split(b";")[0], 16)
        if size == 0:
            while (await reader.readuntil(b"\r\n")) != b"\r\n":
                pass
            return
        data = await reader.readexactly(size)
        if await reader.readexactly(2) != b"\r\n":
            raise ConnectionError("upstream chunk not CRLF-terminated")
        yield data


async def _fire_async(point: str) -> None:
    """The fault seam, async-safe: the engine's seams fire from the
    decode thread where ``time.sleep`` (the delay action) is the
    point, but the router runs ON the event loop — a delay fired
    inline would freeze every concurrent relay and the health poll,
    modeling a frozen router instead of one slowed hop. Disarmed cost
    stays one module-global bool check; armed, the call (sleep or
    raise) runs in a worker thread and propagates."""
    if faults.armed:
        await asyncio.get_running_loop().run_in_executor(
            None, faults.fire, point
        )


async def _close_writer(writer) -> None:
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionResetError, BrokenPipeError, OSError):
        pass


async def _get_json(
    host: str, port: int, path: str, timeout_s: float
) -> dict:
    """One GET against a replica control endpoint (healthz/metrics):
    fresh connection, bounded by ``timeout_s`` end to end."""

    async def _go():
        reader, writer = await asyncio.open_connection(host, port)
        try:
            writer.write(
                (
                    f"GET {path} HTTP/1.1\r\nhost: {host}\r\n"
                    "connection: close\r\n\r\n"
                ).encode()
            )
            await writer.drain()
            status, headers = await _read_response_head(reader)
            clen = headers.get(b"content-length")
            if clen is not None:
                body = await reader.readexactly(int(clen))
            elif headers.get(b"transfer-encoding", b"").lower() == b"chunked":
                body = b"".join([c async for c in _iter_chunked(reader)])
            else:
                body = await reader.read()
            if status != 200:
                raise ConnectionError(f"{path} -> {status}")
            return json.loads(body)
        finally:
            await _close_writer(writer)

    return await asyncio.wait_for(_go(), timeout_s)


class Router:
    """The routing core + forwarding engine. Pure asyncio, no jax, no
    device: every decision reads the cached :class:`ReplicaState`
    table and two integers of per-request hashing."""

    def __init__(
        self,
        endpoints: list[tuple[str, int]],
        *,
        policy: str = "affinity",
        affinity_prefix_bytes: int = DEFAULT_AFFINITY_PREFIX_BYTES,
        health_poll_s: float = 0.5,
        poll_timeout_s: float = 2.0,
        queue_depth_limit: int | None = None,
        assume_live: bool = True,
        rng: random.Random | None = None,
        roles: list | None = None,
    ):
        if not endpoints:
            raise ValueError("router needs at least one replica endpoint")
        if policy not in ("affinity", "round_robin"):
            raise ValueError(f"unknown routing policy {policy!r}")
        if roles is None:
            roles = ["mixed"] * len(endpoints)
        if len(roles) != len(endpoints):
            raise ValueError("one role per replica endpoint")
        bad = [r for r in roles if r not in ("prefill", "decode", "mixed")]
        if bad:
            raise ValueError(f"unknown replica roles {bad!r}")
        self.replicas = [
            ReplicaState(h, p, assume_live=assume_live, role=role)
            for (h, p), role in zip(endpoints, roles)
        ]
        # Role-split topology (r18): disaggregate generative traffic
        # whenever BOTH pools exist. An all-mixed fleet (default) has
        # neither — routing is bit-identical to r17.
        self.role_split = any(r == "prefill" for r in roles) and any(
            r == "decode" for r in roles
        )
        self._xfer_seq = 0
        if len({r.name for r in self.replicas}) != len(self.replicas):
            raise ValueError("duplicate replica endpoints")
        self.policy = policy
        self.affinity_prefix_bytes = int(affinity_prefix_bytes)
        self.health_poll_s = float(health_poll_s)
        self.poll_timeout_s = float(poll_timeout_s)
        self.queue_depth_limit = queue_depth_limit
        # Seeded: the p2c sample must not make routing tests flaky;
        # which of two equal-load replicas wins is not a contract.
        self._rng = rng or random.Random(0x5EED)
        self._rr = 0             # round_robin cursor (A/B baseline)
        self._poll_task: asyncio.Task | None = None
        # Counters (exported under router.* on /metrics).
        self.forwarded = 0
        self.affinity_hits = 0
        self.affinity_fallbacks = 0
        self.failovers = 0
        self.shed_no_replica = 0
        self.stream_upstream_errors = 0
        self.warm_peer_hints = 0
        # Disaggregation counters (r18, exported under router.role_*):
        # disagg_forwards counts two-hop role-split forwards;
        # fallback_mixed counts role-starved degradations (a pool
        # down/unroutable ⇒ the request served mixed-style by
        # whatever is routable); push_incomplete counts handoffs
        # whose transfer failed mid-push (the decode replica then
        # cold-prefills — pages conserved on both ends).
        self.role_disagg_forwards = 0
        self.role_fallback_mixed = 0
        self.role_push_incomplete = 0
        # Multi-model fleets (r22): forwards that found NO replica
        # advertising the requested model and degraded to the whole
        # routable set (the replica then 404s an id it truly lacks —
        # an honest error beats a router-synthesized one during a
        # rolling deploy where the next poll may find the model).
        self.model_fallbacks = 0

    # -- discovery/keys ---------------------------------------------------
    @staticmethod
    def parse_body(body: bytes) -> dict | None:
        """ONE parse of a ``/generate`` body, shared by the routing
        key and the disagg gate (the role-split hot path must not pay
        two full ``json.loads`` of a multi-KB prompt on the event
        loop). ``None`` for unparseable/non-object bodies — the
        replica owns rejecting those."""
        try:
            obj = json.loads(body)
        except Exception:
            return None
        return obj if isinstance(obj, dict) else None

    def routing_key(self, body: bytes) -> bytes | None:
        """The affinity key of a ``/generate`` body (convenience
        wrapper over :meth:`routing_key_of` for callers holding raw
        bytes)."""
        return self.routing_key_of(self.parse_body(body))

    def routing_key_of(self, obj: dict | None) -> bytes | None:
        """The affinity key of a parsed body: the ``prefix`` field
        when present (the shared-prompt cache unit — every request
        naming it must land where its KV lives), else the ``adapter``
        id (a tenant's requests land where its LoRA slot — and, when
        it also uses prefixes, its prefix KV — is already warm), else
        the prompt ``text``; truncated to the first K bytes. The
        router tokenizes nothing — raw UTF-8 bytes hash the same on
        every router process. ``None`` (unparseable body, no text)
        routes by load only; the replica still owns rejecting the bad
        body."""
        if obj is None:
            return None
        src = obj.get("prefix") or obj.get("adapter") or obj.get("text")
        if not isinstance(src, str) or not src:
            return None
        return src.encode("utf-8", "surrogatepass")[
            : self.affinity_prefix_bytes
        ]

    def wants_disagg(self, body: bytes) -> bool:
        """Raw-bytes wrapper over :meth:`wants_disagg_of`."""
        return self.wants_disagg_of(self.parse_body(body))

    def wants_disagg_of(self, obj: dict | None) -> bool:
        """Should this parsed ``/generate`` body take the role-split
        two-hop path? Only in a role-split fleet, and only for plain
        prompt requests: a ``prefix``-carrying request is the
        shared-prefix warmth workload the affinity + peer-fetch path
        (r14/r17) already serves — its suffix prefill is small by
        construction, so disaggregating it buys nothing and would
        complicate the prefix-region transfer. Unparseable bodies
        route normally (the replica owns rejecting them). Adapter
        requests stay single-hop too: the prefill replica would need
        the tenant's slot resident just to run the prompt, doubling
        every adapter's working-set across both role pools for no
        prefill win."""
        if not self.role_split or obj is None:
            return False
        return (
            isinstance(obj.get("text"), str)
            and bool(obj.get("text"))
            and not obj.get("prefix")
            and not obj.get("adapter")
        )

    def _pick_role(
        self, key: bytes | None, role: str,
        exclude: ReplicaState | None = None,
    ) -> ReplicaState | None:
        """The routable pick inside ONE role pool: HRW by key first
        (decode replicas keep per-key placement stable across
        requests — the warmth argument, applied to the role pool),
        power-of-two-choices otherwise; ``None`` when the pool has no
        routable member (the caller degrades to mixed routing,
        counted). Never touches the affinity hit/fallback counters —
        those describe the r14 single-hop policy."""
        now = time.monotonic()
        pool = [
            r for r in self.replicas
            if r.role == role and r is not exclude
            and r.routable(now, self.queue_depth_limit)
        ]
        if not pool:
            return None
        if key is not None:
            order = hrw_order(key, [r.name for r in pool])
            return next(r for r in pool if r.name == order[0])
        if len(pool) == 1:
            return pool[0]
        a, b = self._rng.sample(pool, 2)
        return a if a.load() <= b.load() else b

    # -- the routing decision ---------------------------------------------
    def preferred_for(self, key: bytes | None) -> ReplicaState | None:
        """The HRW head for ``key`` over ALL configured replicas —
        state-independent on purpose: it answers "who is most likely
        WARM for this prefix", which survives the preferred replica
        being down, draining, shedding, or over its depth limit (a
        draining replica still serves ``GET /kv/prefix``; a down one
        just costs the fetcher a fast refused connect). ``None``
        under round-robin or without a key — there is no warmth map
        to consult."""
        if key is None or self.policy != "affinity":
            return None
        order = hrw_order(key, [r.name for r in self.replicas])
        return next(r for r in self.replicas if r.name == order[0])

    def _serves(self, r: ReplicaState, model: str | None) -> bool:
        """Does this replica serve ``model``? The default model is
        everywhere (every process has one); a named model needs the
        replica's advertised set — a replica that never advertised
        one (single-model build, unpolled) serves the default only."""
        if model is None or model == "default":
            return True
        return r.models is not None and model in r.models

    def choose(
        self,
        key: bytes | None,
        exclude: ReplicaState | None = None,
        count: bool = True,
        model: str | None = None,
    ) -> ReplicaState:
        """Pick the replica for one request. Affinity first: the HRW
        top choice over ALL configured replicas (states excluded — the
        preference map must stay stable while a replica drains and
        comes back, or its cache investment is lost on every blip);
        the fallback ladder below it is power-of-two-choices over the
        routable set. ``model`` narrows every rung to the replica
        group advertising that id — an empty group degrades to the
        whole fleet, counted (``router.model_fallbacks``). Raises
        :class:`NoReplicaAvailable` when the routable set is empty."""
        now = time.monotonic()
        cands = [r for r in self.replicas if r is not exclude]
        if model is not None:
            group = [r for r in cands if self._serves(r, model)]
            if group:
                cands = group
            elif count:
                self.model_fallbacks += 1
        routable = [
            r for r in cands if r.routable(now, self.queue_depth_limit)
        ]
        if not routable:
            # Shed with the earliest time a shed window reopens (min 1s
            # so clients don't hammer a draining fleet).
            wait = [r.shed_until - now for r in cands if r.shed_until > now]
            raise NoReplicaAvailable(max(1.0, min(wait)) if wait else 1.0)
        if self.policy == "round_robin":
            r = routable[self._rr % len(routable)]
            self._rr += 1
            return r
        if key is not None:
            order = hrw_order(key, [r.name for r in cands])
            preferred = next(r for r in cands if r.name == order[0])
            if preferred.routable(now, self.queue_depth_limit):
                if count:
                    self.affinity_hits += 1
                return preferred
            if count:
                self.affinity_fallbacks += 1
        if len(routable) == 1:
            return routable[0]
        a, b = self._rng.sample(routable, 2)
        return a if a.load() <= b.load() else b

    # -- health / backpressure polling ------------------------------------
    async def start(self) -> None:
        """One immediate poll round (so a CLI router starts with real
        state, not assumptions), then the background cadence."""
        await self._poll_round()
        self._poll_task = asyncio.create_task(self._poll_loop())

    async def stop(self) -> None:
        if self._poll_task is not None:
            self._poll_task.cancel()
            try:
                await self._poll_task
            except asyncio.CancelledError:
                pass
            self._poll_task = None

    async def _poll_loop(self) -> None:
        while True:
            await asyncio.sleep(self.health_poll_s)
            await self._poll_round()

    async def _poll_round(self) -> None:
        await asyncio.gather(
            *(self._poll_one(r) for r in self.replicas),
            return_exceptions=True,
        )

    async def _poll_one(self, r: ReplicaState) -> None:
        try:
            health = await _get_json(
                r.host, r.port, "/healthz", self.poll_timeout_s
            )
        except Exception:
            r.poll_failures += 1
            # Two consecutive failures = down, not one: a single slow
            # scrape under load must not dump the replica's whole
            # affinity slice onto its peers.
            if r.poll_failures >= 2 and r.state != DOWN:
                _log.warning("replica %s marked down (poll failures)", r.name)
                r.state = DOWN
            return
        # Queue depth (the p2c load signal and the threshold check, at
        # most one tick stale): this repo's replicas surface the
        # /metrics queue-depth gauge on /healthz too, so liveness +
        # backpressure cost ONE connection per tick; a replica without
        # the field (older build, foreign server) falls back to
        # scraping its /metrics gauges — and a replica that is healthy
        # but cannot serve THAT scrape stays live with depth 0
        # (liveness already succeeded; no load signal is not an
        # outage).
        if "queue_depth" in health:
            depth = health["queue_depth"]
        else:
            try:
                gauges = (
                    await _get_json(
                        r.host, r.port, "/metrics", self.poll_timeout_s
                    )
                ).get("gauges", {})
                depth = gauges.get(
                    "generate.queue_depth",
                    gauges.get("batcher.queue_depth", 0),
                )
            except Exception:
                depth = 0
        r.poll_failures = 0
        prev = r.state
        r.state = (
            DRAINING if health.get("status") == "draining" else LIVE
        )
        if prev != r.state:
            _log.info("replica %s: %s -> %s", r.name, prev, r.state)
        r.queue_depth = int(depth or 0)
        r.healthz = health
        m = health.get("models")
        r.models = frozenset(m) if isinstance(m, dict) else None
        r.last_poll = time.monotonic()

    def _note_conn_failure(self, r: ReplicaState) -> None:
        """A refused/failed connect is better evidence than a stale
        poll: stop routing there NOW; the poll loop resurrects it."""
        if r.state != DOWN:
            _log.warning("replica %s marked down (connect failure)", r.name)
        r.state = DOWN

    # -- forwarding --------------------------------------------------------
    def external_depth(self, r: ReplicaState) -> int:
        """Fleet backlog EXCLUDING ``r``'s own share (scraped queue
        depths + router-side inflight of every OTHER replica): the
        backpressure signal forwarded to the replica on each request
        (``x-mlapi-router-depth``). Affinity means a replica's
        repeated prefixes cannot be served elsewhere, so fleet
        pressure is its future queue wait too — the replica feeds
        this into ``admission_estimate_ms()`` and the brownout
        ladder (ROADMAP item-3 → item-1 coupling). DOWN replicas are
        excluded: their scraped depth is frozen at the last
        successful poll, and a crashed replica's stale backlog must
        not keep the survivors shedding/browning out forever."""
        return max(0, sum(
            x.queue_depth + x.inflight
            for x in self.replicas if x is not r and x.state != DOWN
        ))

    def _build_upstream(self, request: Request, r: ReplicaState,
                        warm_peer: ReplicaState | None = None,
                        extra: dict | None = None) -> bytes:
        target = request.scope.get("raw_path") or request.path.encode()
        if isinstance(target, str):  # ASGI test transports pass str
            target = target.encode()
        # Spec-compliant ASGI servers keep the query string OUT of
        # raw_path (this repo's own server stuffs the full target in);
        # re-attach it so forwarded endpoints never silently lose
        # their parameters under uvicorn-style servers.
        query = request.scope.get("query_string") or b""
        if query and b"?" not in target:
            target += b"?" + query
        head = bytearray(
            b"%s %s HTTP/1.1\r\n" % (request.method.encode(), target)
        )
        head += b"host: %s\r\n" % r.name.encode()
        for k, v in request.scope.get("headers", []):
            # x-mlapi-router-depth and x-mlapi-warm-peer are
            # router-authored below; a copy of a client-sent (or
            # upstream-router-sent) one would let callers spoof fleet
            # pressure into the replica's admission estimate — or aim
            # the replica's KV fetches at an arbitrary host.
            if k.lower() not in _HOP_HEADERS and k.lower() not in (
                b"x-mlapi-router-depth",
                b"x-mlapi-warm-peer",
                # r18 disaggregation headers are router-authored too:
                # a client-sent copy could aim a prefill replica's KV
                # pushes at an arbitrary host or claim a staged
                # transfer it never produced.
                b"x-mlapi-decode-peer",
                b"x-mlapi-kv-xfer",
                # The tenant marker is router-authored from the
                # body's validated adapter id — a client-sent copy is
                # an impersonation/header-injection vector.
                b"x-mlapi-adapter",
                # The model marker is router-authored from the
                # registered route (r22) — same rule.
                b"x-mlapi-model",
            ):
                head += k + b": " + v + b"\r\n"
        head += b"content-length: %d\r\n" % len(request.body)
        # Router backpressure rides every forwarded request: the
        # fleet's backlog as this router sees it, minus the target's
        # own share (it knows its own queue better than our poll).
        head += b"x-mlapi-router-depth: %d\r\n" % self.external_depth(r)
        if warm_peer is not None:
            # Warmth hint (r17): this forward misses the key's
            # HRW-preferred replica — name it, so the target can
            # fetch the prefix KV from where it is warm instead of
            # cold-prefilling (--kv-peer-fetch replicas; others
            # ignore the header).
            head += b"x-mlapi-warm-peer: %s\r\n" % warm_peer.name.encode()
        for k, v in (extra or {}).items():
            head += b"%s: %s\r\n" % (k.encode(), v.encode())
        head += b"connection: close\r\n\r\n"
        return bytes(head) + request.body

    @staticmethod
    def _relay_headers(headers: dict[bytes, bytes]) -> dict[str, str]:
        return {
            k.decode("latin-1"): v.decode("latin-1")
            for k, v in headers.items()
            if k not in _HOP_HEADERS
        }

    async def _attempt(self, r: ReplicaState, request: Request,
                       warm_peer: ReplicaState | None = None,
                       extra: dict | None = None) -> Response:
        """One forward attempt against one replica. Returns the relay
        response (unary fully read; streams as a relaying iterator).
        Raises :class:`_SubmitError` on pre-commit failures."""
        try:
            # Bounded connect: a black-holed replica (packet-dropping
            # partition, not a refusal) must fail into the retryable
            # pre-submit path in seconds, not the OS's ~2-minute TCP
            # connect timeout.
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(r.host, r.port),
                self.poll_timeout_s,
            )
        except (OSError, asyncio.TimeoutError) as e:
            self._note_conn_failure(r)
            raise _SubmitError(
                f"connect to replica {r.name} failed: {e}", retryable=True
            ) from None
        r.inflight += 1
        stream_owns = False
        try:
            try:
                # The router_forward SUBMIT seam: fires BEFORE the
                # first request byte leaves the router, so a failover
                # after an injected raise can never duplicate work.
                await _fire_async("router_forward")
            except faults.InjectedFault as e:
                raise _SubmitError(
                    f"injected fault before submit to {r.name}: {e}",
                    retryable=True,
                ) from None
            submitted = False
            try:
                writer.write(
                    self._build_upstream(request, r, warm_peer, extra)
                )
                await writer.drain()
                submitted = True
                status, headers = await _read_response_head(reader)
            except (
                OSError,
                asyncio.IncompleteReadError,
                asyncio.LimitOverrunError,  # absurd upstream head size
                ConnectionError,
                ValueError,
            ) as e:
                self._note_conn_failure(r)
                # Request bytes on the wire with no response: the
                # replica MAY have started generating — no retry.
                raise _SubmitError(
                    f"replica {r.name} failed "
                    f"{'mid-response' if submitted else 'mid-submit'}: {e}",
                    retryable=not submitted,
                ) from None

            chunked = (
                headers.get(b"transfer-encoding", b"").lower() == b"chunked"
            )
            if not chunked:
                try:
                    clen = headers.get(b"content-length")
                    if clen is not None:
                        n = int(clen)
                        body = await reader.readexactly(n) if n else b""
                    else:
                        # No length and not chunked: close-delimited
                        # body (HTTP/1.1-legal, and our own
                        # "connection: close" request invites it from
                        # foreign upstreams) — read to EOF, same as
                        # the poll path's _get_json.
                        body = await reader.read()
                except (asyncio.IncompleteReadError, ValueError) as e:
                    # Truncated body / unparseable framing: a complete
                    # response never arrived, but the request DID — a
                    # 502, never a retry (the generation may have run).
                    raise _SubmitError(
                        f"replica {r.name} sent a malformed response: {e}",
                        retryable=False,
                    ) from None
                # The replica's own content-type rides in via the
                # relayed headers (Response's default is overridden by
                # the same-key entry in ``headers``).
                resp = Response(
                    body,
                    status=status,
                    headers=self._relay_headers(headers),
                )
                if status == 503:
                    # The replica shed at its door (overload, draining,
                    # pool exhaustion) — no work started, failover is
                    # safe. Honor its retry-after as this replica's
                    # shed window so the next requests skip it without
                    # waiting for a poll tick.
                    try:
                        after = float(headers.get(b"retry-after", b"1"))
                    except ValueError:
                        after = 1.0
                    r.shed_until = time.monotonic() + min(after, 5.0)
                    raise _SubmitError(
                        f"replica {r.name} shed 503",
                        retryable=True,
                        response=resp,
                    )
                return resp

            # Streaming relay: status is known, hand the body off to
            # the generator. The generator takes its OWN inflight
            # count and owns the connection — so a relay iterator the
            # asgi layer never starts (client gone in the gap between
            # handler return and first iteration) cannot leak the
            # count that feeds routability.
            stream_owns = True
            return StreamingResponse(
                self._relay_stream(r, reader, writer),
                status=status,
                headers=self._relay_headers(headers),
            )
        finally:
            r.inflight -= 1
            if not stream_owns:
                await _close_writer(writer)

    async def _relay_stream(self, r: ReplicaState, reader, writer):
        """Chunk-for-chunk NDJSON passthrough. Body bytes are relayed
        verbatim — the replica's terminal frames (``done``,
        ``deadline_exceeded``, ``draining``) reach the client
        byte-for-byte. An upstream failure mid-stream appends a
        well-formed error terminal frame; it NEVER retries (the tokens
        already relayed cannot be unsent) and never truncates."""
        r.inflight += 1
        try:
            try:
                async for chunk in _iter_chunked(reader):
                    # The router_forward MID-STREAM seam: one fire per
                    # relayed chunk (call-counted with the submit fires
                    # — after=N skips the submits).
                    await _fire_async("router_forward")
                    yield chunk
            except Exception as e:
                # CancelledError (the client disconnecting) is NOT
                # caught: it propagates so the asgi layer closes us,
                # and the finally tears the upstream down — which
                # cancels the replica's decode work like any client
                # disconnect would.
                self.stream_upstream_errors += 1
                _log.warning(
                    "upstream %s failed mid-stream: %r", r.name, e
                )
                yield json.dumps(
                    {
                        "error": (
                            f"replica {r.name} failed mid-stream: {e}"
                        ),
                        "code": "upstream_error",
                    }
                ).encode() + b"\n"
        finally:
            r.inflight -= 1
            await _close_writer(writer)

    def _hint_for(self, pref: ReplicaState | None,
                  target: ReplicaState) -> ReplicaState | None:
        """The warm-peer hint for one forward: the key's HRW head
        whenever the target is NOT it (fallback, failover, depth
        overflow, post-drain remap — every hop that loses warmth).
        Counted, so the e2e tests can assert hinting happened from
        the router side."""
        if pref is None or pref is target:
            return None
        self.warm_peer_hints += 1
        return pref

    async def forward(
        self, request: Request, key: bytes | None = None,
        adapter: str | None = None, model: str | None = None,
    ) -> Response:
        """Route + forward one request, with the failover-once rule:
        at most one extra hop, and only for submits that provably
        never started work (connect failure, pre-submit injected
        fault, a whole-response 503). ``model`` routes within that
        model's replica group (r22) and stamps the router-authored
        ``x-mlapi-model`` marker on the hop."""
        self.forwarded += 1
        extra = None
        if model is not None:
            # Router-authored like x-mlapi-adapter below (client
            # copies are stripped in _build_upstream); the id charset
            # was validated at route-registration time, so no header
            # injection is possible through it.
            extra = {"x-mlapi-model": model}
        if adapter:
            from mlapi_tpu.serving.adapter_store import ADAPTER_ID_RE

            # Router-authored tenant marker on the hop (client copies
            # are stripped in _build_upstream). Validated against the
            # id charset BEFORE entering a header line — an id with
            # CR/LF or other junk would be header injection; such a
            # body forwards unmarked and the replica rejects it.
            if ADAPTER_ID_RE.match(adapter):
                extra = {**(extra or {}), "x-mlapi-adapter": adapter}
        # The key's HRW head, computed ONCE over all replicas and
        # threaded through BOTH attempts: the failover's second
        # choose() has no memory of the preferred replica (it
        # excludes the failed first and re-ranks the rest), so
        # without this the warm-peer hint would not survive the
        # retry hop — exactly the hop that needs it most.
        pref = self.preferred_for(key)
        try:
            first = self.choose(key, model=model)
        except NoReplicaAvailable as e:
            self.shed_no_replica += 1
            return json_response(
                {"detail": "no live replica available"},
                503,
                headers={"retry-after": str(int(e.retry_after_s))},
            )
        try:
            return await self._attempt(
                first, request, self._hint_for(pref, first), extra
            )
        except _SubmitError as e1:
            if e1.retryable:
                try:
                    # count=False: the request already charged its
                    # affinity hit/fallback on the first choose — the
                    # failover hop landing on the HRW runner-up is
                    # not a second "hit" (it missed its real
                    # preferred replica; failovers counts it).
                    second = self.choose(
                        key, exclude=first, count=False, model=model
                    )
                except NoReplicaAvailable:
                    second = None
                if second is not None:
                    self.failovers += 1
                    _log.info(
                        "failover %s -> %s (%s)",
                        first.name, second.name, e1.detail,
                    )
                    try:
                        return await self._attempt(
                            second, request,
                            self._hint_for(pref, second), extra,
                        )
                    except _SubmitError as e2:
                        return self._submit_error_response(e2, e1)
            return self._submit_error_response(e1)

    async def forward_disagg(
        self, request: Request, key: bytes | None
    ) -> Response:
        """The role-split two-hop forward (r18): hop 1 sends the
        request to a PREFILL replica (p2c by load — prompt work is
        bursty and has no warmth to preserve) naming the HRW-chosen
        DECODE replica and a fresh transfer id; the prefill replica
        streams each finished chunk's KV straight to the decode
        replica and answers with the handoff verdict. Hop 2 forwards
        the client's request to that decode replica — with the
        transfer id only when every chunk landed, so the decode
        replica either installs the pushed KV (zero prefill FLOPs)
        or cold-prefills, never waits on a wire. The fallback ladder
        degrades a role-starved fleet to MIXED routing, counted: no
        routable decode replica ⇒ the plain r14 path over whatever
        is routable; no routable prefill replica ⇒ the decode
        replica takes the cold prefill itself."""
        dec = self._pick_role(key, "decode")
        if dec is None:
            # Decode pool down: whatever is routable serves the whole
            # request, r14-style.
            self.role_fallback_mixed += 1
            return await self.forward(request, key)
        pre = self._pick_role(None, "prefill")
        if pre is None:
            # Prefill pool down: a routable replica (the decode pool,
            # in practice) accepts the cold prefill via the PLAIN
            # forward — which keeps the failover-once ladder, so a
            # decode replica dying between the health poll and this
            # forward still fails over instead of erroring the client
            # in the already-degraded state.
            self.role_fallback_mixed += 1
            return await self.forward(request, key)
        self.forwarded += 1
        self.role_disagg_forwards += 1
        self._xfer_seq += 1
        xfer = f"xf{self._xfer_seq}-{self._rng.getrandbits(48):012x}"
        complete = False
        try:
            resp = await self._attempt(
                pre, request,
                extra={
                    "x-mlapi-decode-peer": dec.name,
                    "x-mlapi-kv-xfer": xfer,
                },
            )
            if resp.status != 200:
                # The prefill replica REJECTED the request itself
                # (422 and friends): relay — the decode replica would
                # reject the same body the same way.
                return resp
            try:
                obj = json.loads(resp.body)
            except Exception:
                obj = {}
            if not obj.get("handoff"):
                # A replica that ignored the role headers (older
                # build, operator-mislabeled role) served the whole
                # generation: that IS the answer — relay it.
                return resp
            complete = bool(obj.get("complete"))
        except _SubmitError as e:
            _log.info(
                "prefill hop to %s failed (%s); decode replica "
                "cold-prefills", pre.name, e.detail,
            )
        if not complete:
            self.role_push_incomplete += 1
        try:
            return await self._attempt(
                dec, request,
                extra={"x-mlapi-kv-xfer": xfer} if complete else None,
            )
        except _SubmitError as e1:
            if e1.retryable:
                # Failover-once, decode pool first: the pushed KV
                # died with the target, so the alternate always
                # cold-prefills (no xfer header).
                second = self._pick_role(key, "decode", exclude=dec)
                if second is None:
                    try:
                        second = self.choose(key, exclude=dec, count=False)
                    except NoReplicaAvailable:
                        second = None
                if second is not None:
                    self.failovers += 1
                    _log.info(
                        "disagg failover %s -> %s (%s)",
                        dec.name, second.name, e1.detail,
                    )
                    try:
                        return await self._attempt(second, request)
                    except _SubmitError as e2:
                        return self._submit_error_response(e2, e1)
            return self._submit_error_response(e1)

    @staticmethod
    def _submit_error_response(
        e: _SubmitError, prior: _SubmitError | None = None
    ) -> Response:
        # Prefer relaying a real replica response (its 503 carries the
        # retry-after the client should honor) over synthesizing one.
        for err in (e, prior):
            if err is not None and err.response is not None:
                return err.response
        return json_response(
            {"detail": f"upstream replica failure: {e.detail}"}, 502
        )

    # -- observability ------------------------------------------------------
    def _state_counts(self) -> dict[str, int]:
        counts = {LIVE: 0, DRAINING: 0, DOWN: 0}
        for r in self.replicas:
            counts[r.state] += 1
        return counts

    def health_snapshot(self) -> dict:
        """The router-level ``/healthz``: ok while at least one
        replica is routable (the layer above should keep sending
        traffic), degraded otherwise."""
        now = time.monotonic()
        counts = self._state_counts()
        routable = sum(
            r.routable(now, self.queue_depth_limit) for r in self.replicas
        )
        # Per-model replica groups (r22): the health rollup of each
        # advertised model id — routable members vs total advertisers.
        # Discovered from the polls, so an all-single-model fleet has
        # no groups and the block is absent (bit-identical to r21).
        groups: dict = {}
        for r in self.replicas:
            for mid in r.models or ():
                g = groups.setdefault(mid, {"routable": 0, "total": 0})
                g["total"] += 1
                g["routable"] += int(
                    r.routable(now, self.queue_depth_limit)
                )
        return {
            "status": "ok" if routable else "degraded",
            **({"model_groups": groups} if groups else {}),
            "router": True,
            "policy": self.policy,
            "affinity_prefix_bytes": self.affinity_prefix_bytes,
            "replicas_live": counts[LIVE],
            "replicas_draining": counts[DRAINING],
            "replicas_down": counts[DOWN],
            "replicas": [
                {
                    "name": r.name,
                    "state": r.state,
                    **({"role": r.role} if self.role_split else {}),
                    "queue_depth": r.queue_depth,
                    "inflight": r.inflight,
                    "shedding": now < r.shed_until,
                    "last_poll_age_s": (
                        round(now - r.last_poll, 3)
                        if r.last_poll is not None
                        else None
                    ),
                }
                for r in self.replicas
            ],
        }

    async def metrics_snapshot(self) -> dict:
        """The aggregated ``/metrics``: counters SUMMED across
        replicas (a counter is a rate source — the fleet total is the
        meaningful number), gauges LABELED per replica (a gauge is a
        state — summing two queue depths hides the hot replica), plus
        the router's own counters and state gauges. Scrapes are fresh
        (this endpoint is the fleet dashboard); a replica that fails
        its scrape contributes its last polled snapshot, flagged
        stale."""
        results = await asyncio.gather(
            *(
                _get_json(r.host, r.port, "/metrics", self.poll_timeout_s)
                for r in self.replicas
            ),
            return_exceptions=True,
        )
        counters: dict = {}
        gauges: dict = {}
        stale = []
        for r, snap in zip(self.replicas, results):
            if isinstance(snap, BaseException):
                snap = r.metrics  # last good scrape, may be {}
                stale.append(r.name)
            else:
                r.metrics = snap
                # A fresh scrape is a better load signal than the last
                # poll tick; fold it into the routing state too.
                g = snap.get("gauges", {})
                r.queue_depth = int(
                    g.get(
                        "generate.queue_depth",
                        g.get("batcher.queue_depth", r.queue_depth),
                    )
                    or 0
                )
            for k, v in snap.get("counters", {}).items():
                if isinstance(v, (int, float)):
                    counters[k] = counters.get(k, 0) + v
            for k, v in snap.get("gauges", {}).items():
                gauges[f"replica.{r.name}.{k}"] = v
        counters["router.forwarded"] = self.forwarded
        counters["router.affinity_hits"] = self.affinity_hits
        counters["router.affinity_fallbacks"] = self.affinity_fallbacks
        counters["router.failovers"] = self.failovers
        counters["router.shed_no_replica"] = self.shed_no_replica
        counters["router.stream_upstream_errors"] = (
            self.stream_upstream_errors
        )
        counters["router.warm_peer_hints"] = self.warm_peer_hints
        if self.role_split:
            # Role-split fleets only: an all-mixed topology's
            # /metrics stays bit-identical to r17.
            counters["router.role_disagg_forwards"] = (
                self.role_disagg_forwards
            )
            counters["router.role_fallback_mixed"] = (
                self.role_fallback_mixed
            )
            counters["router.role_push_incomplete"] = (
                self.role_push_incomplete
            )
        if any(r.models is not None for r in self.replicas) or (
            self.model_fallbacks
        ):
            # Multi-model fleets only — same bit-identity rule as the
            # role-split block above.
            counters["router.model_fallbacks"] = self.model_fallbacks
        state_counts = self._state_counts()
        gauges["router.replicas_live"] = state_counts[LIVE]
        gauges["router.replicas_draining"] = state_counts[DRAINING]
        gauges["router.replicas_down"] = state_counts[DOWN]
        for r in self.replicas:
            gauges[f"router.replica.{r.name}.queue_depth"] = r.queue_depth
            gauges[f"router.replica.{r.name}.inflight"] = r.inflight
        return {
            "counters": counters,
            "gauges": gauges,
            "replicas_stale": stale,
        }


def build_router_app(router: Router, model_ids=None) -> App:
    """The router as an ASGI app on the framework's own server: the
    replica API surface forwarded (``/generate`` with affinity,
    ``/predict`` and ``/files/`` by load), plus the router-level
    ``/healthz`` and aggregated ``/metrics``. ``model_ids`` (the
    supervisor's ``--model`` ids, r22) additionally fronts
    ``/models/<id>/{generate,predict}``, each routed within that
    model's replica group. Handlers take the raw request — the
    REPLICA owns validation, so a 422 relays with the exact byte
    shape a direct client would have seen."""
    import re as _re

    app = App(title="mlapi-tpu-router")
    app.state["router"] = router

    @app.on_startup
    async def _start():
        faults.arm_from_env()
        await router.start()
        _log.info(
            "routing over %d replicas (%s)",
            len(router.replicas), router.policy,
        )

    @app.on_shutdown
    async def _stop():
        await router.stop()

    @app.post("/generate")
    async def generate(request: Request):
        obj = router.parse_body(request.body)  # parsed ONCE
        key = router.routing_key_of(obj)
        if router.wants_disagg_of(obj):
            # Role-split fleet + plain prompt: the two-hop
            # prefill→decode path (r18). Prefix-carrying requests
            # stay on the affinity path below — their warmth story is
            # the r14/r17 machinery; adapter-carrying ones too (the
            # gate above keeps a tenant's slot working-set on ONE
            # replica).
            return await router.forward_disagg(request, key)
        aid = obj.get("adapter") if obj else None
        return await router.forward(
            request, key=key,
            adapter=aid if isinstance(aid, str) else None,
        )

    @app.post("/predict")
    async def predict(request: Request):
        # No prefix economics on classification rows: route by load
        # (power of two choices over the routable set) — unless the
        # row names a tenant adapter, which routes by the same HRW
        # affinity as /generate (the tenant's slot lives somewhere).
        obj = router.parse_body(request.body)
        aid = obj.get("adapter") if obj else None
        if isinstance(aid, str) and aid:
            return await router.forward(
                request,
                key=aid.encode("utf-8", "surrogatepass")[
                    : router.affinity_prefix_bytes
                ],
                adapter=aid,
            )
        return await router.forward(request)

    def _install_model_routes(mid: str) -> None:
        # Closure-per-id, like app.py's per-model install loop: the
        # route table is static (exact-path match, no params), built
        # once from the same --model set the replicas serve.
        @app.post(f"/models/{mid}/generate")
        async def model_generate(request: Request, _mid=mid):
            obj = router.parse_body(request.body)
            aid = obj.get("adapter") if obj else None
            return await router.forward(
                request, key=router.routing_key_of(obj),
                adapter=aid if isinstance(aid, str) else None,
                model=_mid,
            )

        @app.post(f"/models/{mid}/predict")
        async def model_predict(request: Request, _mid=mid):
            return await router.forward(request, model=_mid)

    for mid in model_ids or ():
        if not _re.fullmatch(r"[A-Za-z0-9._-]+", mid):
            raise ValueError(f"model id {mid!r} is not URL-path-safe")
        _install_model_routes(mid)

    @app.post("/files/")
    async def files(request: Request):
        return await router.forward(request)

    @app.get("/healthz")
    async def healthz():
        return router.health_snapshot()

    @app.get("/metrics")
    async def metrics():
        return await router.metrics_snapshot()

    return app
