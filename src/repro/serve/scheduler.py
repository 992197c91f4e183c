"""Continuous-batching request scheduler on top of the scan-fused engine.

A fixed pool of ``max_batch`` decode *slots* serves a queue of requests:

  * **admit** — a free slot prefils the next queued request and its caches
    are written into the slot's row of the batched cache pytree (and, in
    paged mode, scattered into freshly allocated KV blocks);
  * **decode** — all slots step together through a fused ``lax.scan`` chunk
    of ``decode_chunk`` tokens (one host roundtrip per chunk, not per
    token), with *per-row* positions (every slot sits at its own depth);
  * **evict** — a request leaves its slot when it emits ``eos_id`` or hits
    its ``max_new_tokens``; its blocks return to the free list and the slot
    is immediately re-admittable.

KV layouts (``cfg.kv``):

  * ``"paged"`` (default) — attention KV lives in a per-layer *block pool*
    ``(n_blocks, block_size, KH*Dh)`` of lane-dense rows addressed through
    a per-slot block table; the decode layer scan writes and gathers the
    layer-stacked pools in place.  Block 0 is the trash block: idle/evicted
    slots point at it, so their decode writes land in memory nobody reads,
    and prefill scatters use drop-mode sentinels so pad positions write
    nowhere at all.  A request only occupies
    ``ceil((plen + max_new)/block_size)`` blocks (plus
    ``ceil(window/block_size)`` for sliding-window layers), so short
    requests don't reserve worst-case capacity — admission is bounded by
    free *blocks*, not uniform slot capacity.
  * ``"dense"`` — the PR 3 layout: every slot owns a capacity-sized cache
    row.  Kept as the bit-exactness oracle for the paged path.

Prompt handling (``cfg.buckets``):

  * a tuple of lengths — prompts are right-padded up to a bucket, so
    prefill compiles once per bucket.  Pad exactness: pad positions write
    cache slots *ahead* of the request's position (dense) or are dropped
    outright (paged); the per-row valid mask hides the rest — bit-identical
    to an unpadded prefill.  Sliding-window layers need
    ``max(buckets) <= cfg.window`` (pads would evict real history from the
    rolling prefill cache), and recurrent blocks (R/S) / enc-dec are
    rejected — their prefill state would integrate the pad tokens.
  * ``None`` — exact-length prefill (compiles per distinct prompt length;
    ``cfg.max_prompt`` bounds capacity).  No pad tokens exist, which lifts
    the window limit and admits *every* model family: recurrent (R) and
    SSM (S) state live in dense per-slot rows, and encoder-decoder models
    keep per-slot cross-attention buffers with per-row valid lengths
    (``cn``), so slots can hold encoder contexts of different lengths.

Fault-tolerant serving keeps **per-request reliability accounting**: each
request draws its faults from its own key stream ``fold_in(base, rid)``
folded by its own token index, carried through the batch as an (B, 2) key
array (``FTCtx`` per-row mode).  Row b's fault draws — and its quantization
scales — depend only on request b, so evicting or admitting neighbours
never perturbs another request's generation.  This holds with
``policy.weight_faults`` too: the reference and fused backends draw
*per-row* weight flip words, giving each request its own independent
faulty-weight view of the shared SRAM.  ``ft_backend`` may be
``"reference"`` or ``"fused"`` (the fused Pallas decode kernel — same
draws, bit-identical tokens).

Sharded serving: pass ``mesh=`` and every executable runs under GSPMD with
the serving layout (see ``Scheduler.__init__`` and docs/serving.md §Sharded
serving).  Counter-based RNG keeps every per-request fault stream — and
therefore every temp-0 token — bit-identical to the 1-device run.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.parallel import sharding as S
from repro.parallel.ctx import mesh_ctx


@dataclasses.dataclass
class Request:
    rid: int
    tokens: list                     # prompt token ids
    max_new_tokens: int = 16
    extras: dict | None = None       # e.g. {"patch_embeds": (P, D)} for VLMs
    # filled by the scheduler:
    generated: list = dataclasses.field(default_factory=list)
    finish_reason: str | None = None   # "eos" | "length"


@dataclasses.dataclass
class SchedulerConfig:
    max_batch: int = 4               # concurrent decode slots
    buckets: tuple | None = (8, 16)  # prompt pad lengths; None = exact-length
    max_prompt: int | None = None    # prompt cap when buckets is None
    max_new_tokens: int = 16         # per-request cap (cache headroom)
    decode_chunk: int = 4            # fused scan steps per host roundtrip
    temperature: float = 0.0
    eos_id: int = -1                 # < 0: no EOS eviction
    seed: int = 0
    kv: str = "paged"                # "paged" | "dense" KV-cache layout
    block_size: int = 8              # tokens per KV block (paged)
    n_blocks: int | None = None      # pool size incl. trash block (paged;
    #                                  default: full provisioning)


# host phases of ``Scheduler.run``, each a profiler span and a counter;
# prefill, first_token and insert nest in admit
PHASES = ("serve.admit", "serve.prefill", "serve.first_token", "serve.insert",
          "serve.chunk", "serve.readback", "serve.harvest", "serve.retire")


@dataclasses.dataclass
class SchedStats:
    prefill_calls: int = 0
    insert_calls: int = 0
    chunk_calls: int = 0
    retire_calls: int = 0
    tokens: int = 0
    blocks_in_use_peak: int = 0
    readbacks: int = 0               # device-to-host copies
    # per phase: host seconds in its spans, and its longest single span
    host_s: dict = dataclasses.field(default_factory=dict)
    host_max_s: dict = dataclasses.field(default_factory=dict)

    @property
    def roundtrips(self) -> int:
        return (self.prefill_calls + self.insert_calls + self.chunk_calls
                + self.retire_calls)

    @contextlib.contextmanager
    def phase(self, name: str, **args):
        """Time one host phase into ``host_s`` / ``host_max_s`` and mark it
        as a ``jax.profiler`` span (``args`` become the span's stats), on
        the profiler's clock when a trace is being taken."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name, **args):
            yield
        dt = time.perf_counter() - t0
        self.host_s[name] = self.host_s.get(name, 0.0) + dt
        self.host_max_s[name] = max(self.host_max_s.get(name, 0.0), dt)


class Scheduler:
    def __init__(self, model, params, cfg: SchedulerConfig | None = None,
                 policy=None, ft_backend: str = "reference", ft_t=None,
                 mesh=None):
        """``mesh``: a jax Mesh — params are device_put in the serving layout
        (TP over 'model', DP-replicated), the slot caches are sharded per
        ``parallel.sharding.cache_shardings`` (batch over DP, heads over
        'model', paged pools DP-replicated), and all four executables
        (prefill / insert / chunk / retire) run under the mesh's activation
        constraints.  Per-request fault streams are unchanged: threefry is
        counter-based, so a request's draws are bit-identical at TP=1 and
        TP=N (tests/test_serve_sharded.py proves it)."""
        from repro.ft import as_policy
        self.model, self.params = model, params
        self.cfg = cfg or SchedulerConfig()
        self.policy = as_policy(policy)
        self.stats = SchedStats()
        self.mesh = mesh
        ctx = S.make_ctx(mesh) if mesh is not None else None
        if mesh is not None:
            self.params = jax.device_put(
                params, S.param_shardings(params, mesh, no_fsdp=True))

        def _shard_caches(caches):
            if mesh is None:
                return caches
            return jax.lax.with_sharding_constraint(
                caches, S.cache_shardings(caches, mesh,
                                          kv_heads=model.cfg.n_kv_heads))

        mcfg = model.cfg
        kinds = T._layer_kinds(mcfg)
        exact = self.cfg.buckets is None
        if self.cfg.kv not in ("paged", "dense"):
            raise ValueError(f"unknown kv layout {self.cfg.kv!r}")
        if set(kinds) & {"R", "S"} or mcfg.enc_dec:
            if not exact:
                raise ValueError(
                    "bucketed prefill supports attention families only: "
                    "right-padded prompts would integrate pad tokens into "
                    "recurrent/encoder state.  Recurrent (R/S) and enc-dec "
                    "models schedule with buckets=None (exact-length "
                    "prefill); their recurrent/SSM state lives in dense "
                    "per-slot rows under either kv layout")
        self._front = (mcfg.n_frontend_tokens if mcfg.frontend == "vision"
                       else 0)
        if (not exact and "L" in kinds
                and self._front + max(self.cfg.buckets) > mcfg.window):
            raise ValueError(
                f"buckets {self.cfg.buckets} (+ {self._front} frontend "
                f"tokens) exceed the sliding window {mcfg.window}: pad "
                "tokens would evict real history from the rolling cache "
                "(use buckets=None for exact-length prefill)")
        if exact and self.cfg.max_prompt is None:
            raise ValueError("buckets=None (exact-length prefill) needs "
                             "cfg.max_prompt to bound slot capacity")
        if self.policy is not None and ft_backend not in ("reference",
                                                          "fused"):
            raise ValueError(
                "per-request fault streams need ft_backend='reference' or "
                "'fused' (per-row keys, per-row weight-fault streams); the "
                "pallas backend takes a single global key and a static t")

        # cache capacity: every slot can hold the largest admitted prompt
        # plus a full generation
        max_prompt = (self.cfg.max_prompt if exact
                      else max(self.cfg.buckets))
        self.capacity = max_prompt + self.cfg.max_new_tokens + self._front
        self._window = mcfg.window if "L" in kinds else 0
        bs = self.cfg.block_size
        self._wg = -(-self.capacity // bs)
        self._wl = -(-self._window // bs) if self._window else 0
        if self.cfg.kv == "paged":
            self.n_blocks = (self.cfg.n_blocks
                             if self.cfg.n_blocks is not None
                             else 1 + self.cfg.max_batch
                             * (self._wg + self._wl))
            if self.n_blocks < 2:
                raise ValueError("paged KV needs n_blocks >= 2 (block 0 is "
                                 "the trash block)")
        else:
            self.n_blocks = 0

        base = jax.random.PRNGKey(self.cfg.seed)
        ftbase, sbase = jax.random.split(base)
        self._ftbase, self._sbase = ftbase, sbase
        temperature = self.cfg.temperature
        capacity = self.capacity
        window = self._window

        def _ftc(keys):
            if self.policy is None:
                return None
            from repro.models.common import FTCtx
            return FTCtx(self.policy, keys, backend=ft_backend, t=ft_t)

        def _sample(logits, keys, tsteps):
            if temperature <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            def one(k, t, lg):
                return jax.random.categorical(
                    jax.random.fold_in(k, t + 1), lg / temperature)
            return jax.vmap(one)(keys, tsteps, logits).astype(jnp.int32)

        def _prefill_one(params, batch1, last_idx, rid):
            # per-request streams: prefill draws from fold(fold(base, rid), 0)
            # (B=1, so a single stream per call is already per-request)
            with mesh_ctx(ctx):
                ftk = jax.random.fold_in(jax.random.fold_in(ftbase, rid), 0)
                caches, logits = model.prefill(params, batch1,
                                               max_len=capacity,
                                               ftc=_ftc(ftk),
                                               last_index=last_idx)
                skey = jax.random.fold_in(sbase, rid)
                tok0 = _sample(logits, skey[None],
                               jnp.full((1,), -1, jnp.int32))
                return caches, tok0[0]

        def _scatter_pool(pool, rows, bt_row, wdw, plen):
            # pool (..., P, bs, KH*Dh), a layer's or stacked over layers;
            # rows (..., 1, S1, KH, Dh).  Prefill position p lands at row
            # p % bs of its logical block's physical block; positions past
            # the request's real length (bucket pads, capacity growth) get
            # the sentinel block P and are dropped — they write nowhere.
            # A stacked pool's layer dim is indexed too, not sliced: a
            # scatter at (layer, block, offset) updates the stack in place,
            # where a sliced layer dim makes XLA relayout the whole stack.
            P = pool.shape[-3]
            S1 = rows.shape[-3]
            idx = jnp.arange(S1)
            valid_n = jnp.minimum(plen, wdw) if wdw else plen
            blk = jnp.where(idx < valid_n, bt_row[idx // bs], P)
            lay = ((jnp.arange(pool.shape[0])[:, None],) if pool.ndim == 4
                   else ())
            rows = rows.reshape(*pool.shape[:-3], S1, pool.shape[-1])
            return pool.at[(*lay, blk, idx % bs)].set(rows, mode="drop")

        def _insert(caches, c1, slot, plen, bt_g, bt_l):
            # one executable for both layouts: paged attention leaves are
            # scattered through the slot's new block table; dense leaves
            # (dense KV, R/S state, cross-attn buffers) are slot-row writes
            def upd(buf, new, stacked):
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, new, slot, 1 if stacked else 0)

            def layer(pc, dc, kind, stacked):
                wdw = window if kind == "L" else 0
                out = {}
                for nm, sub in pc.items():
                    dsub = dc.get(nm)
                    if (nm == "attn" and isinstance(sub, dict)
                            and "bt" in sub):
                        row = bt_l if wdw else bt_g
                        scat = partial(_scatter_pool, bt_row=row, wdw=wdw,
                                       plen=plen)
                        out[nm] = {
                            "k": scat(sub["k"], dsub["k"]),
                            "v": scat(sub["v"], dsub["v"]),
                            "bt": sub["bt"].at[..., slot, :].set(row),
                        }
                    elif nm == "cross":
                        s1e = dsub["ck"].shape[-3]
                        if stacked:
                            start = (0, slot, 0, 0, 0)
                            cn = sub["cn"].at[:, slot].set(s1e)
                        else:
                            start = (slot,) + (0,) * (sub["ck"].ndim - 1)
                            cn = sub["cn"].at[slot].set(s1e)
                        out[nm] = {
                            "ck": jax.lax.dynamic_update_slice(
                                sub["ck"], dsub["ck"], start),
                            "cv": jax.lax.dynamic_update_slice(
                                sub["cv"], dsub["cv"], start),
                            "cn": cn,
                        }
                    else:
                        out[nm] = jax.tree.map(
                            lambda b, n: upd(b, n, stacked), sub, dsub)
                return out

            mcfg_ = model.cfg
            kinds_ = T._layer_kinds(mcfg_)
            if mcfg_.unroll:
                out = {f"l{i}": layer(caches[f"l{i}"], c1[f"l{i}"],
                                      kinds_[i], False)
                       for i in range(len(kinds_))}
            else:
                out = {}
                for si, (pattern, _) in enumerate(mcfg_.segments):
                    out[f"seg{si}"] = {
                        f"s{j}": layer(caches[f"seg{si}"][f"s{j}"],
                                       c1[f"seg{si}"][f"s{j}"], kind, True)
                        for j, kind in enumerate(pattern)}
            return _shard_caches(out)

        def _retire(caches, slot):
            # point the evicted slot's block tables back at the trash block
            # so its (still-stepping) row stops writing into blocks that may
            # be reallocated to a new request
            def one(path, leaf):
                names = [str(getattr(k, "key", "")) for k in path]
                if names and names[-1] == "bt":
                    if names[0].startswith("seg"):
                        return leaf.at[:, slot].set(0)
                    return leaf.at[slot].set(0)
                return leaf
            return jax.tree_util.tree_map_with_path(one, caches)

        def _chunk(params, caches, tok, pos, tstep, rids, active, n_steps):
            act = active.astype(jnp.int32)

            def body(carry, _):
                caches, tok, pos, tstep = carry
                keys = jax.vmap(
                    lambda r, t: jax.random.fold_in(
                        jax.random.fold_in(ftbase, r), t + 1))(rids, tstep)
                caches, logits = model.decode_step(params, caches, tok, pos,
                                                   ftc=_ftc(keys))
                skeys = jax.vmap(jax.random.fold_in)(
                    jnp.broadcast_to(sbase, (rids.shape[0],) + sbase.shape),
                    rids)
                nxt = _sample(logits, skeys, tstep)
                tok = jnp.where(active, nxt, tok)
                pos = pos + act
                tstep = tstep + act
                return (_shard_caches(caches), tok, pos, tstep), nxt

            with mesh_ctx(ctx):
                (caches, tok, pos, tstep), toks = jax.lax.scan(
                    body, (caches, tok, pos, tstep), None, length=n_steps)
            return caches, tok, pos, tstep, jnp.moveaxis(toks, 0, 1)

        self._prefill_one = jax.jit(_prefill_one)
        self._insert = jax.jit(_insert, donate_argnums=(0,))
        self._retire_fn = jax.jit(_retire, donate_argnums=(0,))
        self._chunk = jax.jit(_chunk, static_argnums=(7,),
                              donate_argnums=(1,))

    # ------------------------------------------------------------ helpers --
    def _bucket(self, n: int) -> int:
        if self.cfg.buckets is None:
            if n > self.cfg.max_prompt:
                raise ValueError(f"prompt length {n} exceeds cfg.max_prompt "
                                 f"{self.cfg.max_prompt}")
            return n
        for b in sorted(self.cfg.buckets):
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket "
                         f"{max(self.cfg.buckets)}")

    def _make_batch1(self, req: Request):
        L = len(req.tokens)
        Lb = self._bucket(L)
        toks = np.zeros((1, Lb), np.int32)
        toks[0, :L] = req.tokens
        batch1 = {"tokens": jnp.asarray(toks)}
        for k, v in (req.extras or {}).items():
            batch1[k] = jnp.asarray(v)[None]
        last_idx = jnp.asarray([self._front + L - 1], jnp.int32)
        return batch1, last_idx, self._front + L

    def _blocks_needed(self, plen: int, max_new: int) -> int:
        if self.cfg.kv != "paged":
            return 0
        bs = self.cfg.block_size
        total = min(plen + max_new, self.capacity)
        need = -(-total // bs)
        if self._window:
            need += -(-min(total, self._window) // bs)
        return need

    def _init_caches(self, B: int):
        if self.cfg.kv == "paged":
            enc_len = (self.capacity - self.cfg.max_new_tokens
                       if self.model.cfg.enc_dec else None)
            return self.model.init_cache(
                B, self.capacity, paged=(self.cfg.block_size, self.n_blocks),
                enc_len=enc_len)
        return self.model.init_cache(B, self.capacity)

    # ---------------------------------------------------------------- run --
    def run(self, requests) -> dict:
        """Serve `requests` to completion; returns {rid: Request} with
        ``generated`` / ``finish_reason`` filled."""
        cfg = self.cfg
        B = cfg.max_batch
        bs = cfg.block_size
        self.stats = SchedStats()
        seen_rids = set()
        for req in requests:
            plen = self._front + self._bucket(len(req.tokens))  # fail fast
            if req.rid in seen_rids:
                raise ValueError(
                    f"duplicate request id {req.rid}: results are keyed by "
                    "rid and the per-request fault streams derive from it")
            seen_rids.add(req.rid)
            if req.max_new_tokens > cfg.max_new_tokens:
                raise ValueError(
                    f"request {req.rid} wants {req.max_new_tokens} tokens "
                    f"but the slot capacity budgets cfg.max_new_tokens="
                    f"{cfg.max_new_tokens}: decoding past capacity would "
                    "overwrite cache history")
            if self.model.cfg.enc_dec and req.extras:
                fl = np.asarray(req.extras["frames"]).shape[0]
                if fl > self.capacity - cfg.max_new_tokens:
                    raise ValueError(
                        f"request {req.rid} encoder input length {fl} "
                        f"exceeds the cross-attention capacity "
                        f"{self.capacity - cfg.max_new_tokens} "
                        "(cfg.max_prompt)")
            if (cfg.kv == "paged"
                    and self._blocks_needed(plen, req.max_new_tokens)
                    > self.n_blocks - 1):
                raise ValueError(
                    f"request {req.rid} needs "
                    f"{self._blocks_needed(plen, req.max_new_tokens)} KV "
                    f"blocks but the pool has {self.n_blocks - 1} "
                    "allocatable: raise cfg.n_blocks or block_size")
            req.generated = []              # a re-submitted Request restarts
            req.finish_reason = None
        queue = collections.deque(requests)
        slots: list[Request | None] = [None] * B
        out = {}

        caches = self._init_caches(B)
        if self.mesh is not None:
            caches = jax.device_put(
                caches, S.cache_shardings(
                    caches, self.mesh, kv_heads=self.model.cfg.n_kv_heads))
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tstep = np.zeros((B,), np.int32)
        rids = np.zeros((B,), np.int32)
        free_blocks = collections.deque(range(1, self.n_blocks))
        slot_blocks: list[list] = [[] for _ in range(B)]

        def alloc_tables(plen, max_new):
            """Pop blocks for a request; return (bt_g, bt_l) table rows."""
            total = min(plen + max_new, self.capacity)
            g_need = -(-total // bs)
            l_need = (-(-min(total, self._window) // bs)
                      if self._window else 0)
            got = [free_blocks.popleft() for _ in range(g_need + l_need)]
            bt_g = np.zeros((self._wg,), np.int32)
            bt_g[:g_need] = got[:g_need]
            bt_l = np.zeros((max(self._wl, 1),), np.int32)
            if l_need:
                bt_l[:l_need] = got[g_need:]
            return got, jnp.asarray(bt_g), jnp.asarray(bt_l)

        def release(s):
            if cfg.kv == "paged":
                free_blocks.extend(slot_blocks[s])
                slot_blocks[s] = []

        def finish(s, req, reason):
            req.finish_reason = reason
            out[req.rid] = req
            slots[s] = None
            release(s)

        phase = self.stats.phase

        while queue or any(s is not None for s in slots):
            # ---- admit into free slots (a request that finishes at
            # prefill — EOS first token or max_new_tokens == 1 — does not
            # use up the slot's turn; the slot retries the queue) ---------
            admitted = 0
            for s in range(B):
                while slots[s] is None and queue:
                    req = queue[0]
                    need = self._blocks_needed(
                        self._front + self._bucket(len(req.tokens)),
                        req.max_new_tokens)
                    if need > len(free_blocks):
                        break               # wait for evictions to free blocks
                    queue.popleft()
                    with phase("serve.admit", rid=req.rid):
                        batch1, last_idx, plen = self._make_batch1(req)
                        with phase("serve.prefill", rid=req.rid):
                            c1, tok0 = self._prefill_one(
                                self.params, batch1, last_idx,
                                jnp.asarray(req.rid, jnp.int32))
                        self.stats.prefill_calls += 1
                        with phase("serve.first_token", rid=req.rid):
                            t0 = int(tok0)
                        self.stats.readbacks += 1
                        req.generated.append(t0)
                        self.stats.tokens += 1
                        if cfg.eos_id >= 0 and t0 == cfg.eos_id:
                            req.finish_reason = "eos"
                            out[req.rid] = req
                            continue
                        if len(req.generated) >= req.max_new_tokens:
                            req.finish_reason = "length"
                            out[req.rid] = req
                            continue
                        if cfg.kv == "paged":
                            got, bt_g, bt_l = alloc_tables(
                                plen, req.max_new_tokens)
                            slot_blocks[s] = got
                            in_use = self.n_blocks - 1 - len(free_blocks)
                            self.stats.blocks_in_use_peak = max(
                                self.stats.blocks_in_use_peak, in_use)
                        else:
                            bt_g = jnp.zeros((self._wg,), jnp.int32)
                            bt_l = jnp.zeros((max(self._wl, 1),), jnp.int32)
                        with phase("serve.insert", rid=req.rid):
                            caches = self._insert(
                                caches, c1, jnp.asarray(s, jnp.int32),
                                jnp.asarray(plen, jnp.int32), bt_g, bt_l)
                        self.stats.insert_calls += 1
                    slots[s] = req
                    admitted += 1
                    tok[s], pos[s], tstep[s], rids[s] = t0, plen, 0, req.rid

            active = np.array([r is not None for r in slots])
            if not active.any():
                if queue and not admitted:
                    raise RuntimeError(
                        "scheduler stalled: no active slots and the next "
                        "request cannot be admitted (KV block pool too "
                        "small?)")
                continue

            # ---- one fused decode chunk --------------------------------
            with phase("serve.chunk", active=int(active.sum())):
                caches, tokj, posj, tstepj, toksj = self._chunk(
                    self.params, caches, jnp.asarray(tok), jnp.asarray(pos),
                    jnp.asarray(tstep), jnp.asarray(rids),
                    jnp.asarray(active), cfg.decode_chunk)
            self.stats.chunk_calls += 1
            with phase("serve.readback"):
                # np.array (not asarray): device outputs view as read-only,
                # and the admission path writes slots in place
                tok, pos, tstep = (np.array(tokj), np.array(posj),
                                   np.array(tstepj))
                toks = np.asarray(toksj)                  # (B, chunk)
            self.stats.readbacks += 4

            # ---- harvest + evict ---------------------------------------
            evicted = []
            with phase("serve.harvest"):
                for s in range(B):
                    req = slots[s]
                    if req is None:
                        continue
                    for t in toks[s]:
                        req.generated.append(int(t))
                        self.stats.tokens += 1
                        if cfg.eos_id >= 0 and int(t) == cfg.eos_id:
                            finish(s, req, "eos")
                            evicted.append(s)
                            break
                        if len(req.generated) >= req.max_new_tokens:
                            finish(s, req, "length")
                            evicted.append(s)
                            break
            if cfg.kv == "paged" and evicted:
                with phase("serve.retire"):
                    for s in evicted:
                        caches = self._retire_fn(caches,
                                                 jnp.asarray(s, jnp.int32))
                        self.stats.retire_calls += 1
        return out
