"""Prefill/decode interleave engine (continuous batching).

The MaxText offline-inference shape: ``num_slots`` lanes of one batched
per-slot cache (k/v, or MLA's latent rows: whatever leaves the model's
``init_cache`` declares).  Every tick the engine

1. **admits** — pops arrived requests off the ``RequestQueue`` while free
   lanes exist: each prompt runs chunked fused prefill (``Model.prefill``)
   into a private 1-lane cache, which one jitted insert copies into the
   free lane (slot index and first token are traced, so admission never
   recompiles);
2. **decodes** — one jitted ``Model.decode_step`` over ALL lanes (free
   lanes compute garbage that is simply never read);
3. **bookkeeps** — appends each active lane's greedy token host-side,
   releases lanes whose request hit ``max_new_tokens`` / ``eos_id`` so the
   next tick's admission can refill them.

With ``moe_layer`` set (a ``models.moe.DynamicMoELayer`` built for
``num_tokens == num_slots``), the transformer's MoE FFN is routed through
the §5-priced comm schedule via ``RunCtx.moe_step``: per-tick routing,
in-jit plan derivation, zero host plan builds after the first-tick trace —
asserted through ``comm.telemetry`` (``decode_host_free``).

The engine's clock is the tick counter, so ``Request.arrival_time`` in
tick units makes admission order fully deterministic for tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import telemetry
from repro.kernels.mla_decode import rows_read
from repro.models.transformer import Model
from repro.serve.queue import Request, RequestQueue
from repro.serve.slots import SlotManager

__all__ = ["ServeEngine", "ServeReport", "cache_len_of",
           "generate_batch_loop", "moe_decode_hook"]


def moe_decode_hook(cfg, layer):
    """``RunCtx.moe_step`` adapter: route one decode tick's (B, 1, D)
    hidden batch through a ``DynamicMoELayer``.

    The routing is ``models.moe.route``, the one rule prefill and training
    use too; the dispatch→expert→combine then runs in the layer's fused
    shard_map window with THIS layer's traced weights — one
    ``DynamicMoELayer`` instance (template shapes) serves every scanned
    transformer layer via ``DynamicMoELayer.apply``.  Returns the output,
    the routing (B, k) and the assignments past the layer's capacity.
    """
    from repro.models import moe as M

    def moe_step(p_moe, h):
        b, _, d = h.shape
        with jax.named_scope("moe.route"):
            top_w, top_e = M.route(p_moe["router"], h.reshape(b, d), cfg)
            dropped = M.dropped_assignments(top_e, cfg.num_experts,
                                            layer.capacity)
        wx = [p_moe["w1"], p_moe["w2"]]
        if cfg.act == "swiglu":
            wx.append(p_moe["w3"])
        with jax.named_scope("moe.experts"):
            y = layer.apply(h.reshape(b, d), top_e, top_w, *wx)
        return y.reshape(b, 1, d).astype(h.dtype), top_e, dropped

    return moe_step


def _with_moe_hook(model: Model, moe_layer) -> Model:
    if moe_layer is None:
        return model
    if model.cfg.family != "moe":
        raise ValueError(
            f"moe_layer needs a MoE model, got family {model.cfg.family!r}")
    ctx = dataclasses.replace(
        model.ctx, moe_step=moe_decode_hook(model.cfg, moe_layer))
    return Model(model.cfg, ctx)


def _insert(cache, prefix, slot, token, tokens):
    """Copy a B=1 per-slot prefix cache into lane ``slot`` of the batched
    cache and seed the lane's next input token.  Layer arrays carry a
    leading stacked-L dim, so every leaf maps as (L, 1, ...) -> lane of
    (L, B, ...), whatever leaves the model's cache declares (k/v, or the
    latent c_kv/k_pe).  A MoE cache's dropped-assignment count adds the
    prefix's."""

    def put(dst, src):
        return dst.at[:, slot].set(src[:, 0])

    out = dict(cache)
    out["layers"] = jax.tree.map(put, cache["layers"], prefix["layers"])
    out["pos"] = cache["pos"].at[slot].set(prefix["pos"][0])
    if "moe" in cache:
        out["moe"] = {**cache["moe"], "dropped": cache["moe"]["dropped"]
                      + prefix["moe"]["dropped"]}
    toks = tokens.at[slot, 0].set(token)
    return out, toks


def _greedy_tick(model: Model):
    """One decode tick and its greedy next tokens (B, 1) in one program, so
    the host waits on the device once a tick and dispatches nothing else.
    A MoE model's routing comes out beside the cache as well: the cache is
    donated to the next tick, the routing outlives it."""

    def decode_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        experts = cache["moe"]["experts"] if "moe" in cache else None
        return logits, nxt[:, None], experts, cache

    return decode_step


def cache_len_of(cache) -> int:
    """The ring length of a per-slot cache, whatever its layout."""
    return int(cache["layers"]["slot_pos"].shape[-1])


def _percentile(xs, q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    i = min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1))))
    return float(s[i])


@dataclasses.dataclass
class ServeReport:
    """What a ``ServeEngine.run`` produced, with the latency accounting
    ``benchmarks.tables.table_serve`` reports."""

    outputs: dict[Any, list[int]]       # request id -> greedy tokens
    completed: list[Any]                # completion order
    slot_of: dict[Any, int]             # request id -> lane it ran in
    ticks: int
    tick_seconds: list[float]           # wall time of each decode tick
    token_seconds: list[float]          # per generated token (its tick's dt)
    ttft_seconds: dict[Any, float]      # request id -> prefill wall time
    telemetry: dict                     # comm.telemetry deltas for the run

    @property
    def total_tokens(self) -> int:
        return sum(len(v) for v in self.outputs.values())

    @property
    def tokens_per_s(self) -> float:
        """Decode throughput: tokens emitted by decode ticks over decode
        wall time (prefill tokens/time excluded on both sides)."""
        t = sum(self.tick_seconds)
        return len(self.token_seconds) / t if t > 0 else 0.0

    def p50_us(self) -> float:
        return _percentile(self.token_seconds, 50.0) * 1e6

    def p99_us(self) -> float:
        return _percentile(self.token_seconds, 99.0) * 1e6


class ServeEngine:
    """Continuous-batching serving loop over a per-slot decode cache."""

    def __init__(self, model: Model, params, *, num_slots: int,
                 cache_len: int, prefill_chunk: int | None = None,
                 moe_layer=None, cache_dtype=None):
        if moe_layer is not None and moe_layer.num_tokens != num_slots:
            raise ValueError(
                f"moe_layer routes {moe_layer.num_tokens} tokens per step "
                f"but the engine decodes {num_slots} lanes; build the "
                f"DynamicMoELayer with num_tokens={num_slots}")
        self.model = _with_moe_hook(model, moe_layer)
        self.params = params
        self.prefill_chunk = prefill_chunk
        self.cache_dtype = cache_dtype or self.model.ctx.act_dtype
        # one traced derivation per MoE layer executes every decode tick
        self._derives_per_tick = (
            model.cfg.num_layers - model.cfg.first_dense_layers
            if moe_layer is not None else 0)

        self.cache = self.model.init_cache(
            num_slots, cache_len, per_slot=True, dtype=self.cache_dtype)
        self.cache_len = cache_len_of(self.cache)
        self.slots = SlotManager(num_slots)
        self.queue = RequestQueue()
        self._tokens = jnp.zeros((num_slots, 1), jnp.int32)
        # every lane's position, as the cache's ``pos`` holds it on the
        # device; an MLA model's decode steps run the latent-attention
        # kernel, whose reads the engine counts from these
        self._pos = np.zeros(num_slots, np.int64)
        self._mla = model.cfg.is_mla
        # the latest finished tick's logits (B, 1, V) and, for a MoE model,
        # every MoE layer's routing (MoE layers, B, k)
        self.last_logits = self.last_experts = None
        self._ahead = None        # a tick dispatched before the step it ends
        self._last_done = 0.0

        # the caches are donated: each call updates its cache in place, so
        # the batched cache is held once, never twice
        self._decode_fn = jax.jit(_greedy_tick(self.model), donate_argnums=1)
        # prefill routes through moe_fwd, even a one-token chunk of one lane
        self._prefill_fn = jax.jit(model.prefill, donate_argnums=1)
        self._insert_fn = jax.jit(_insert, donate_argnums=0)
        self._dropped_seen = 0

        self.now = 0.0            # tick clock (admission compares against it)
        self.ticks = 0
        self._outputs: dict[Any, list[int]] = {}
        self._completed: list[Any] = []
        self._slot_of: dict[Any, int] = {}
        self._tick_seconds: list[float] = []
        self._token_seconds: list[float] = []
        self._ttft: dict[Any, float] = {}
        self._snap0 = telemetry.stats.snapshot()

    # ---- request intake ----
    def submit(self, request: Request) -> None:
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        plen = len(np.asarray(request.prompt).reshape(-1))
        if plen < 1 or plen > self.cache_len:
            raise ValueError(
                f"prompt length {plen} must be in [1, {self.cache_len}] "
                "(the decode cache ring)")
        self.queue.submit(request)

    # ---- one tick ----
    def step(self) -> int:
        """Admit → decode → bookkeep.  Returns the number of lanes still
        active after the tick.

        While the next tick cannot change which lanes decode (no lane ends
        at this tick, none can be admitted), the next tick is dispatched
        before this one's tokens are read back, so the device never waits
        for the host's bookkeeping; that tick then ends the next step, and
        a request submitted meanwhile is admitted a step later."""
        tick, self._ahead = self._ahead, None
        if tick is None:
            while self.slots.num_free and len(self.queue):
                req = self.queue.pop_ready(self.now)
                if req is None:
                    break
                self._admit(req)
            active = self.slots.active()
            if active:
                tick = self._dispatch(active)
        if tick is not None:
            if self._may_run_ahead(tick[-1]):
                self._ahead = self._dispatch(tick[-1])
            self._finish(tick)

        self.now += 1.0
        self.ticks += 1
        return len(self.slots.active())

    def _dispatch(self, active):
        t0 = time.perf_counter()
        logits, self._tokens, experts, self.cache = self._decode_fn(
            self.params, self.cache, self._tokens)
        rows = rows_read(self._pos, self.cache_len) if self._mla else 0
        self._pos += 1
        return t0, logits, self._tokens, experts, rows, active

    def _may_run_ahead(self, active) -> bool:
        """Whether the tick after the one in flight decodes the same lanes:
        none of them ends at it (no eos to wait for) and none can be
        admitted before it."""
        if len(self.queue) and self.slots.num_free:
            return False
        return all(s.eos_id is None and s.generated + 1 < s.max_new_tokens
                   for s in active)

    def _finish(self, tick) -> None:
        t0, self.last_logits, tokens, self.last_experts, rows, active = tick
        nxt_host = np.asarray(tokens)[:, 0]          # blocks: tick boundary
        done = time.perf_counter()
        dt = done - max(t0, self._last_done)
        self._last_done = done
        telemetry.record_tick("decode_steps")
        if self._mla:
            telemetry.record_mla_decode(1, rows)
        for _ in range(self._derives_per_tick):
            telemetry.record("device-derive")
        self._tick_seconds.append(dt)
        for s in active:
            self._token_seconds.append(dt)
            self._emit(s, int(nxt_host[s.index]))

    def _admit(self, req: Request) -> None:
        t0 = time.perf_counter()
        prompt = np.asarray(req.prompt, np.int32).reshape(1, -1)
        plen = prompt.shape[1]
        prefix = self.model.init_cache(
            1, self.cache_len, per_slot=True, dtype=self.cache_dtype)
        chunk = self.prefill_chunk or plen
        logits = None
        with telemetry.span("serve.prefill"):
            for lo in range(0, plen, chunk):
                piece = jnp.asarray(prompt[:, lo:lo + chunk])
                logits, prefix = self._prefill_fn(self.params, prefix, piece)
                telemetry.record_tick("prefill_chunks")
            first = jnp.argmax(logits[0, -1], axis=-1).astype(jnp.int32)
        slot = self.slots.allocate(req.id, max_new_tokens=req.max_new_tokens,
                                   eos_id=req.eos_id)
        self.cache, self._tokens = self._insert_fn(
            self.cache, prefix, jnp.asarray(slot, jnp.int32), first,
            self._tokens)
        self._pos[slot] = plen
        self._outputs[req.id] = []
        self._slot_of[req.id] = slot
        self._ttft[req.id] = time.perf_counter() - t0
        # the prefill's last-position logits yield generated token #1
        self._emit(self.slots[slot], int(first))

    def _emit(self, s, tok: int) -> None:
        rid = s.request_id
        self._outputs[rid].append(tok)
        s.generated += 1
        if s.generated >= s.max_new_tokens or (
                s.eos_id is not None and tok == s.eos_id):
            self._completed.append(rid)
            self.slots.release(s.index)

    # ---- drive to completion ----
    def run(self, *, max_ticks: int = 100_000) -> ServeReport:
        """Tick until the queue drains and every lane completes."""
        while len(self.queue) or self.slots.active():
            if not self.slots.active():
                nxt = self.queue.next_arrival()
                if nxt is not None and nxt > self.now:
                    self.now = float(nxt)       # idle: jump to next arrival
            self.step()
            if self.ticks >= max_ticks:
                raise RuntimeError(f"serve loop exceeded {max_ticks} ticks")
        return self.report()

    def report(self) -> ServeReport:
        """What the engine produced so far; first records the MoE
        assignments dropped since the last report (telemetry
        ``moe_dropped``; reading the device counter waits for the device)."""
        if "moe" in self.cache:
            total = int(self.cache["moe"]["dropped"])
            telemetry.record_moe_dropped(total - self._dropped_seen)
            self._dropped_seen = total
        return ServeReport(
            outputs={k: list(v) for k, v in self._outputs.items()},
            completed=list(self._completed),
            slot_of=dict(self._slot_of),
            ticks=self.ticks,
            tick_seconds=list(self._tick_seconds),
            token_seconds=list(self._token_seconds),
            ttft_seconds=dict(self._ttft),
            telemetry=telemetry.stats.since(self._snap0),
        )

    # ---- steady-state invariant ----
    def snapshot(self) -> dict:
        """Telemetry snapshot for a later ``assert_steady_state``."""
        return telemetry.stats.snapshot()

    def assert_steady_state(self, snap: dict) -> dict:
        """Assert ZERO host plan builds happened across the decode ticks
        since ``snap`` (the §5 T_plan tax must not recur once warm).
        Returns the telemetry delta."""
        delta = telemetry.stats.since(snap)
        if not telemetry.stats.decode_host_free(snap):
            raise AssertionError(
                f"host plan builds during steady-state decode: {delta}")
        return delta


def generate_batch_loop(model: Model, params, requests, *, cache_len: int,
                        prefill_chunk: int | None = None, moe_layer=None,
                        cache_dtype=None) -> dict[Any, list[int]]:
    """The naive batch-loop baseline the engine must match token-for-token.

    Every request gets a dedicated lane up front (batch = len(requests):
    no queue, no admission, no slot reuse), prompts prefill per-request
    into their lanes through the same fused path, then one decode step per
    tick until the longest request finishes — the pre-serve ``launch.serve``
    demo loop.  Tokens stop accumulating per request at its
    ``max_new_tokens`` / ``eos_id``, so outputs compare directly against
    ``ServeReport.outputs``.
    """
    dtype = cache_dtype or model.ctx.act_dtype
    prefill = jax.jit(model.prefill)
    model = _with_moe_hook(model, moe_layer)
    decode = jax.jit(model.decode_step)
    insert = jax.jit(_insert)

    b = len(requests)
    cache = model.init_cache(b, cache_len, per_slot=True, dtype=dtype)
    clen = cache_len_of(cache)
    tokens = jnp.zeros((b, 1), jnp.int32)
    outs: dict[Any, list[int]] = {}

    for i, r in enumerate(requests):
        prompt = np.asarray(r.prompt, np.int32).reshape(1, -1)
        prefix = model.init_cache(1, clen, per_slot=True, dtype=dtype)
        chunk = prefill_chunk or prompt.shape[1]
        logits = None
        for lo in range(0, prompt.shape[1], chunk):
            piece = jnp.asarray(prompt[:, lo:lo + chunk])
            logits, prefix = prefill(params, prefix, piece)
        first = jnp.argmax(logits[0, -1], axis=-1).astype(jnp.int32)
        cache, tokens = insert(cache, prefix, jnp.asarray(i, jnp.int32),
                               first, tokens)
        outs[r.id] = [int(first)]

    def done(r):
        o = outs[r.id]
        return len(o) >= r.max_new_tokens or (
            r.eos_id is not None and o and o[-1] == r.eos_id)

    while not all(done(r) for r in requests):
        logits, cache = decode(params, cache, tokens)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        tokens = nxt[:, None]
        nh = np.asarray(nxt)
        for i, r in enumerate(requests):
            if not done(r):
                outs[r.id].append(int(nh[i]))
    return outs
