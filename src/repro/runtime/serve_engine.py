"""ServeEngine: continuous-batching request engine over the decode executor.

The serving counterpart of the training executor's plan machinery: where
``runtime/serve_loop.py`` provides the jitted *tick* (one donated,
mesh-sharded ``decode_step`` over a fixed slot batch), this module provides
the *request* layer on top of it —

  * **admission queue + slot batcher** — a fixed batch of ``n_slots``
    decode slots ticks together; a finished request's slot is refilled on
    the next tick (continuous batching), with per-slot ``active`` masks and
    a per-slot ``pos`` vector threaded through ``Model.decode_step``.
    ``continuous=False`` degrades to the static baseline (admission only
    when every slot has drained) the bench validator compares against.
  * **paged KV pool** — full-attention KV families share one pool of
    physical ``block_size``-position blocks (``Model.paged_cache_specs``)
    addressed per-slot through a block table, so a short prompt holds
    blocks proportional to its length, not worst case; block 0 is reserved
    as the garbage target for inactive-slot writes.  Fixed-size cache
    families (SWA rings, RWKV wkv state, mamba/hybrid SSD state) instead
    swap whole per-slot cache rows at admission.  Pool exhaustion evicts
    the youngest request, which is requeued with its generated prefix as
    prompt — deterministic per-request sampling keys make the replay exact.
  * **prefill/decode disaggregation** — prompts prefill in length-bucketed
    shapes (bounded jit-shape set) via ``Model.prefill(lens=...)``, then
    the cache rows/blocks are spliced into the live pool and the request
    joins the decode tick.  Recurrent families prefill at exact length:
    right-padding would pollute the state summary.
  * **sampling + stop conditions** — temperature/top-p with per-request
    seeds (``runtime/sampling.py``); stop tokens, ``max_new_tokens``, and
    the ``cache_len`` capacity cap, all per request.

Every finished request emits a ``repro.telemetry/1`` ``request`` record
(arrival/admit/first-token/done timestamps, token counts, finish reason).
Each tick and its parts are host spans (``core/telemetry.py:span``), with
the tick's counters as attributes:

  serve.tick          the whole of :meth:`ServeEngine.step`: ``tick``,
                      ``n_slots``, ``live`` (slots decoded), ``admitted``,
                      ``kv_positions`` (positions the decoded slots attend),
                      ``free_blocks`` (after growth), ``evicted``
  serve.admit         one admission: ``rid``, ``prompt_len`` (replayed
                      prefix included), ``bucket``, ``replay``
  serve.prefill       its prefill and the scatter into the cache (``rid``)
  serve.grow_blocks   block growth: ``blocks`` taken, ``evictions``
  serve.decode        the decode step and the sampler's dispatch: ``attn``
                      (``paged_kernel`` or ``gather``, the path the paged
                      pool's attention takes; None without a pool),
                      ``kv_blocks`` (pool blocks the decoded slots attend)
  serve.wait          a host pull that waits on the device: ``for`` is
                      ``prefill`` (the first token) or ``decode``
  serve.bookkeep      token accounting, finishes and releases: ``finished``
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry as tel
from repro.models import blocks
from repro.models.common import Spec, init_params
from repro.models.model import Model
from repro.runtime import serve_loop
from repro.runtime.sampling import sample_tokens


@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival`` is seconds relative to the run
    start (the engine clock); ``extras`` carries non-token prefill inputs
    (``frames`` (T, fd) for encdec, ``patches`` (P, fd) for vlm)."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    stop_tokens: tuple[int, ...] = ()
    arrival: float = 0.0
    extras: dict | None = None


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0                # host mirror of cache pos (incl. patch offset)
    next_token: int = 0         # token id fed at the next decode tick
    blocks: list[int] = dataclasses.field(default_factory=list)
    admit_seq: int = 0


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class ServeEngine:
    """See module docstring.  ``mesh``/``plan`` attach GSPMD shardings to
    the tick (slot batch on the data axis, cache seq/pool on the model
    axis); without them everything runs single-device jitted."""

    def __init__(self, model: Model, params: Any, *, n_slots: int = 4,
                 cache_len: int = 64, block_size: int = 8,
                 n_blocks: int | None = None, max_blocks: int | None = None,
                 prefill_buckets: tuple[int, ...] | None = None,
                 continuous: bool = True, mesh: Any = None, plan: Any = None,
                 telemetry_sink: Any = None,
                 clock: Callable[[], float] = time.monotonic):
        cfg = model.cfg
        self.model, self.params, self.cfg = model, params, cfg
        self.n_slots, self.cache_len = n_slots, cache_len
        self.continuous = continuous
        self.mesh, self.plan = mesh, plan
        self.sink = telemetry_sink
        self.clock = clock
        self.paged = model.paged_cacheable
        self.patch_off = cfg.num_patches if cfg.family == "vlm" else 0
        # recurrent state summarizes every fed position, so padded prefill
        # would pollute it — these families prefill at exact prompt length
        self.exact_prefill = cfg.family in ("rwkv", "hybrid")

        # the attention path the compiled decode takes over the paged pool
        self.attn_route = None
        if self.paged:
            self.attn_route = ("paged_kernel" if blocks.paged_kernel_route(
                quant=cfg.kv_quant, unsharded=mesh is None) else "gather")
            self.block_size = block_size
            cap = cache_len + self.patch_off
            self.max_blocks = max_blocks or (cap // block_size + 1)
            # default pool: worst case for every slot, +1 garbage block —
            # undersize it (n_blocks=) to exercise eviction
            self.n_blocks = n_blocks or (1 + n_slots * self.max_blocks)
            self.cache_specs = model.paged_cache_specs(
                n_slots, self.n_blocks, block_size)
            self.free_blocks = list(range(self.n_blocks - 1, 0, -1))
            self.bt = np.zeros((n_slots, self.max_blocks), np.int32)
        else:
            self.block_size = block_size
            self.max_blocks = None
            self.cache_specs = model.cache_specs(n_slots, cache_len)
            # engine contract: pos is a per-slot vector
            self.cache_specs["pos"] = Spec((n_slots,), ("cache_batch",),
                                           init="zeros", dtype=jnp.int32)
        if prefill_buckets is None:
            b, buckets = max(4, block_size), []
            while b < cache_len:
                buckets.append(b)
                b *= 2
            prefill_buckets = tuple(buckets) + (cache_len,)
        self.prefill_buckets = tuple(sorted(prefill_buckets))

        self.cache = init_params(self.cache_specs, jax.random.PRNGKey(0))
        if mesh is not None:
            assert plan is not None
            _, csh = serve_loop.cache_sds_and_shardings(
                model, n_slots, cache_len, mesh, plan,
                cache_specs=self.cache_specs)
            self.cache = jax.device_put(self.cache, csh)
            self._decode = serve_loop.build_decode_step(
                model, mesh, plan, n_slots, cache_len,
                cache_specs=self.cache_specs,
                batch_specs=serve_loop.decode_batch_specs(
                    cfg, n_slots, engine=True, max_blocks=self.max_blocks))
        else:
            self._decode = serve_loop.build_decode_step(model)
        self._prefills: dict[int, Any] = {}
        self._admit_fn = jax.jit(self._make_admit(), donate_argnums=(0,))
        self._encode = jax.jit(model.encode) if cfg.family == "encdec" else None
        if cfg.family == "encdec":
            self.memory = jnp.zeros(
                (n_slots, cfg.enc_seq_len, cfg.d_model), jnp.float32)

        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue: collections.deque[Request] = collections.deque()
        self.results: dict[int, dict] = {}
        self.records: list[dict] = []
        # per-slot sampler knobs, updated at admission
        self.temps = np.zeros(n_slots, np.float32)
        self.top_ps = np.ones(n_slots, np.float32)
        self.seeds = np.zeros(n_slots, np.int32)
        self.steps = np.zeros(n_slots, np.int32)
        self._admit_seq = 0
        self._t0 = self.clock()
        self.n_ticks = 0
        self.n_prefills = 0
        self.n_evictions = 0

    # ------------------------------------------------------------------
    # Capacity
    # ------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Max total positions (prompt + generated + patches) per request."""
        cap = self.cache_len + self.patch_off
        if self.paged:
            cap = min(cap, self.max_blocks * self.block_size - 1)
        return cap

    def _now(self) -> float:
        return self.clock() - self._t0

    # ------------------------------------------------------------------
    # Jitted cache splice ops
    # ------------------------------------------------------------------
    def _make_admit(self):
        """Build the donated cache-splice op: paged mode scatters the
        bucket-prefill KV blocks into the pool through per-request physical
        targets (pad targets -> garbage block 0); slot mode overwrites one
        whole cache row.  Leaf layout is spec-driven — the ``cache_blocks``
        / ``cache_batch`` axis position differs across families (moe_every
        nests a second layer stack)."""
        specs = self.cache_specs["layers"]
        bs = self.block_size

        if self.paged:
            def admit(cache, small_layers, targets, slot, new_pos):
                def place(spec, pool, small):
                    i = spec.axes.index("cache_blocks")
                    sm = jnp.squeeze(small, axis=i)   # drop the unit batch
                    nb = sm.shape[i] // bs
                    sm = sm.reshape(sm.shape[:i] + (nb, bs) + sm.shape[i + 1:])
                    idx = (slice(None),) * i + (targets,)
                    return pool.at[idx].set(sm.astype(pool.dtype))

                new = dict(cache)
                new["pos"] = cache["pos"].at[slot].set(new_pos)
                new["layers"] = jax.tree.map(
                    place, specs, cache["layers"], small_layers,
                    is_leaf=lambda x: isinstance(x, Spec))
                return new
            return admit

        def admit(cache, small, slot, new_pos):
            def place(spec, c, p):
                i = spec.axes.index("cache_batch")
                idx = (slice(None),) * i + (slot,)
                return c.at[idx].set(jnp.squeeze(p, axis=i).astype(c.dtype))

            new = dict(cache)
            new["pos"] = cache["pos"].at[slot].set(new_pos)
            new["layers"] = jax.tree.map(
                place, specs, cache["layers"], small["layers"],
                is_leaf=lambda x: isinstance(x, Spec))
            if "shared" in cache:
                new["shared"] = jax.tree.map(
                    place, self.cache_specs["shared"], cache["shared"],
                    small["shared"], is_leaf=lambda x: isinstance(x, Spec))
            return new
        return admit

    def _get_prefill(self, bucket: int):
        """Jitted length-bucketed prefill; one compile per bucket shape."""
        if bucket not in self._prefills:
            if self.paged:
                clen = _round_up(bucket + self.patch_off, self.block_size)
            else:
                clen = self.cache_len
            self._prefills[bucket] = serve_loop.build_prefill(
                self.model, clen, with_lens=True)
        return self._prefills[bucket]

    def _bucket(self, length: int) -> int:
        for b in self.prefill_buckets:
            if b >= length:
                return b
        raise ValueError(f"prompt length {length} exceeds largest prefill "
                         f"bucket {self.prefill_buckets[-1]}")

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens + self.patch_off > self.capacity:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"max_new {req.max_new_tokens} exceeds capacity "
                f"{self.capacity}")
        st = self.results.setdefault(req.rid, {
            "generated": [], "t_arrival": self._now(), "t_admit": None,
            "t_first_token": None, "t_done": None, "evictions": 0,
            "finish_reason": None,
        })
        if st["finish_reason"] is not None:
            raise ValueError(f"request {req.rid} already finished")
        self.queue.append(req)

    def _admit_ready(self) -> None:
        if not self.continuous and any(s.req for s in self.slots):
            return  # static batching: wait for the whole batch to drain
        free = [i for i, s in enumerate(self.slots) if s.req is None]
        while free and self.queue:
            req = self.queue[0]
            if self.paged:
                total = len(req.prompt) + self.patch_off + \
                    len(self.results[req.rid]["generated"])
                n_keep = total // self.block_size + 1
                if len(self.free_blocks) < n_keep:
                    # wait for in-flight requests to release blocks —
                    # evicting here would thrash (the victim becomes the
                    # new queue head and displaces another victim)
                    if not any(s.req is not None for s in self.slots):
                        raise RuntimeError(
                            f"request {req.rid} needs {n_keep} blocks; "
                            f"pool has {len(self.free_blocks)} free and "
                            "nothing in flight to wait for")
                    break
            self.queue.popleft()
            self._admit(free.pop(0), req)

    def _admit(self, slot_idx: int, req: Request) -> None:
        st = self.results[req.rid]
        gen = st["generated"]
        # an evicted request replays with its generated prefix as prompt —
        # per-request fold_in keys continue at step len(gen), so the replay
        # reproduces the original stream exactly
        prompt = np.asarray(req.prompt, np.int32)
        if gen:
            prompt = np.concatenate([prompt, np.asarray(gen, np.int32)])
        L = len(prompt)
        total = L + self.patch_off
        bucket = L if self.exact_prefill else self._bucket(L)
        with tel.span("serve.admit", rid=req.rid, prompt_len=L,
                      bucket=bucket, replay=len(gen)):
            with tel.span("serve.prefill", rid=req.rid):
                toks = np.zeros((1, bucket), np.int32)
                toks[:, :L] = prompt
                batch: dict[str, Any] = {"tokens": jnp.asarray(toks)}
                if req.extras:
                    for k, v in req.extras.items():
                        batch[k] = jnp.asarray(v)[None]
                logits, small = self._get_prefill(bucket)(
                    self.params, batch, jnp.asarray([L], np.int32))
                self.n_prefills += 1

                slot = self.slots[slot_idx]
                if self.paged:
                    n_keep = total // self.block_size + 1
                    blocks = [self.free_blocks.pop() for _ in range(n_keep)]
                    nb_bucket = _round_up(bucket + self.patch_off,
                                          self.block_size) // self.block_size
                    nb_real = min(n_keep, nb_bucket)
                    # pad blocks -> garbage
                    targets = np.zeros(nb_bucket, np.int32)
                    targets[:nb_real] = blocks[:nb_real]
                    self.bt[slot_idx] = 0
                    self.bt[slot_idx, :n_keep] = blocks
                    self.cache = self._admit_fn(self.cache, small["layers"],
                                                jnp.asarray(targets),
                                                slot_idx, total)
                    slot.blocks = blocks
                else:
                    self.cache = self._admit_fn(self.cache, small, slot_idx,
                                                total)
                if self._encode is not None:
                    mem = self._encode(
                        self.params, jnp.asarray(req.extras["frames"])[None])
                    self.memory = self.memory.at[slot_idx].set(mem[0])

            slot.req = req
            slot.pos = total
            slot.admit_seq = self._admit_seq
            self._admit_seq += 1
            self.temps[slot_idx] = req.temperature
            self.top_ps[slot_idx] = req.top_p
            self.seeds[slot_idx] = req.seed
            self.steps[slot_idx] = len(gen)
            now = self._now()
            if st["t_admit"] is None:
                st["t_admit"] = now

            # first token of this admission comes straight from prefill logits
            first = sample_tokens(
                logits, jnp.asarray(self.temps[slot_idx:slot_idx + 1]),
                jnp.asarray(self.top_ps[slot_idx:slot_idx + 1]),
                jnp.asarray(self.seeds[slot_idx:slot_idx + 1]),
                jnp.asarray(self.steps[slot_idx:slot_idx + 1]))
            with tel.span("serve.wait", rid=req.rid, **{"for": "prefill"}):
                tok = int(np.asarray(first)[0])
            self._take_token(slot_idx, tok)

    def _take_token(self, slot_idx: int, tok: int) -> None:
        """Account one sampled token for the slot's request; finish or
        queue it as the next tick's input."""
        slot = self.slots[slot_idx]
        req = slot.req
        st = self.results[req.rid]
        st["generated"].append(tok)
        self.steps[slot_idx] += 1
        now = self._now()
        if st["t_first_token"] is None:
            st["t_first_token"] = now
        n_gen = len(st["generated"])
        if tok in req.stop_tokens:
            self._finish(slot_idx, "stop_token")
        elif n_gen >= req.max_new_tokens:
            self._finish(slot_idx, "max_new_tokens")
        elif slot.pos + 1 >= self.capacity:
            self._finish(slot_idx, "capacity")
        else:
            slot.next_token = tok

    def _finish(self, slot_idx: int, reason: str) -> None:
        slot = self.slots[slot_idx]
        st = self.results[slot.req.rid]
        st["t_done"] = self._now()
        st["finish_reason"] = reason
        self._emit_record(slot.req, st)
        self._release(slot_idx)

    def _release(self, slot_idx: int) -> None:
        slot = self.slots[slot_idx]
        if self.paged:
            self.free_blocks.extend(reversed(slot.blocks))
            self.bt[slot_idx] = 0
            slot.blocks = []
        slot.req = None
        slot.pos = 0
        slot.next_token = 0
        self.temps[slot_idx] = 0.0
        self.steps[slot_idx] = 0

    def _evict_one(self, exclude: int | None = None) -> bool:
        """Pool pressure: evict the youngest-admitted request and requeue
        it (front) with its generated prefix; returns False when no slot is
        evictable."""
        cands = [i for i, s in enumerate(self.slots)
                 if s.req is not None and i != exclude]
        if not cands:
            return False
        victim = max(cands, key=lambda i: self.slots[i].admit_seq)
        req = self.slots[victim].req
        self.results[req.rid]["evictions"] += 1
        self.n_evictions += 1
        self._release(victim)
        self.queue.appendleft(req)
        return True

    def _emit_record(self, req: Request, st: dict) -> None:
        rec = {
            "schema": tel.SCHEMA, "kind": "request", "rid": req.rid,
            "arch": self.cfg.name,
            "t_arrival": st["t_arrival"], "t_admit": st["t_admit"],
            "t_first_token": st["t_first_token"], "t_done": st["t_done"],
            "n_prompt": int(len(req.prompt)),
            "n_generated": len(st["generated"]),
            "finish_reason": st["finish_reason"],
            "evictions": st["evictions"],
        }
        rec = tel.sanitize_record(rec)
        tel.validate_record(rec)
        self.records.append(rec)
        if self.sink is not None:
            self.sink.write(rec)

    # ------------------------------------------------------------------
    # The tick
    # ------------------------------------------------------------------
    def _grow_blocks(self) -> None:
        """Allocate the next physical block for any paged slot whose next
        write position crosses its allocation; evict under pressure."""
        with tel.span("serve.grow_blocks") as attrs:
            taken, evicted = 0, self.n_evictions
            for i, slot in enumerate(self.slots):
                while (slot.req is not None
                       and slot.pos // self.block_size >= len(slot.blocks)):
                    if not self.free_blocks:
                        if not self._evict_one(exclude=i):
                            raise RuntimeError(
                                "paged pool exhausted with nothing evictable")
                        continue
                    blk = self.free_blocks.pop()
                    self.bt[i, len(slot.blocks)] = blk
                    slot.blocks.append(blk)
                    taken += 1
            attrs.update(blocks=taken, evictions=self.n_evictions - evicted)

    def step(self) -> list[int]:
        """One engine tick: admissions, paged-block growth, one decode
        step over the slot batch, sampling, stop handling.  Returns the
        rids that finished this tick."""
        with tel.span("serve.tick", tick=self.n_ticks,
                      n_slots=self.n_slots) as attrs:
            prefills, evictions = self.n_prefills, self.n_evictions
            self._admit_ready()
            if self.paged and any(s.req is not None for s in self.slots):
                self._grow_blocks()
            active = [i for i, s in enumerate(self.slots) if s.req is not None]
            attrs.update(
                live=len(active), admitted=self.n_prefills - prefills,
                kv_positions=sum(self.slots[i].pos + 1 for i in active),
                free_blocks=len(self.free_blocks) if self.paged else None,
                evicted=self.n_evictions - evictions)
            if not active:
                return []
            kv_blocks = (sum(-(-(self.slots[i].pos + 1) // self.block_size)
                             for i in active) if self.paged else None)
            with tel.span("serve.decode", attn=self.attn_route,
                          kv_blocks=kv_blocks):
                mask = np.zeros(self.n_slots, bool)
                mask[active] = True
                tokens = np.array([s.next_token for s in self.slots],
                                  np.int32)[:, None]
                batch: dict[str, Any] = {"token": jnp.asarray(tokens),
                                         "active": jnp.asarray(mask)}
                if self.paged:
                    batch["block_table"] = jnp.asarray(self.bt)
                if self._encode is not None:
                    batch["memory"] = self.memory
                logits, self.cache = self._decode(self.params, self.cache,
                                                  batch)
                sampled = sample_tokens(
                    logits, jnp.asarray(self.temps), jnp.asarray(self.top_ps),
                    jnp.asarray(self.seeds), jnp.asarray(self.steps))
            with tel.span("serve.wait", **{"for": "decode"}):
                sampled = np.asarray(sampled)
            self.n_ticks += 1
            with tel.span("serve.bookkeep") as done:
                finished = []
                for i in active:
                    self.slots[i].pos += 1
                    before = self.slots[i].req.rid
                    self._take_token(i, int(sampled[i]))
                    if self.slots[i].req is None:
                        finished.append(before)
                done["finished"] = len(finished)
            return finished

    # ------------------------------------------------------------------
    # Drive to completion
    # ------------------------------------------------------------------
    def run(self, requests: list[Request] | None = None,
            max_ticks: int = 1_000_000) -> dict[int, np.ndarray]:
        """Admit ``requests`` as their ``arrival`` offsets pass on the
        engine clock and tick until everything drains; returns
        ``{rid: generated token ids}``."""
        pending = sorted(requests or [], key=lambda r: (r.arrival, r.rid))
        self._t0 = self.clock()
        i = 0
        ticks = 0
        while (i < len(pending) or self.queue
               or any(s.req is not None for s in self.slots)):
            now = self._now()
            while i < len(pending) and pending[i].arrival <= now:
                self.submit(pending[i])
                i += 1
            if not self.queue and not any(s.req is not None
                                          for s in self.slots):
                # idle until the next arrival
                wait = pending[i].arrival - self._now()
                if wait > 0:
                    time.sleep(min(wait, 0.01))
                continue
            self.step()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(f"engine did not drain in {max_ticks} ticks")
        return {rid: np.asarray(st["generated"], np.int32)
                for rid, st in self.results.items()}
