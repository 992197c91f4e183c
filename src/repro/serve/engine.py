"""Batched serving engine: prefill + scan-fused greedy/temperature decode.

The decode loop is a single ``jax.lax.scan`` executable: the per-step
fault-draw keys are folded *inside* the scan from the step index, the
sampling key is threaded through the carry, and the caches are donated once
at the loop boundary — so a whole generation costs two host dispatches
(prefill + loop) instead of one per token.  ``Engine(loop="python")`` keeps
the legacy per-token dispatch path; at temperature 0 the two paths emit
bit-identical tokens (tests/test_serve_engine.py proves it under every
registry protection policy and both ft backends).

Works on any mesh: passing ``mesh=`` device_puts the params in the serving
layout (``param_shardings(no_fsdp=True)``: TP over 'model', replicated over
the DP axes), shards the input batch over DP, and constrains the caches the
prefill returns — batch-sharded over DP and head-sharded over 'model' (paged
pools stay DP-replicated; see parallel.sharding.cache_shardings).

Fault-tolerant serving: pass a ``repro.ft`` protection policy (object or
registry name) and every projection of prefill and decode computes through
the faulty-DLA path with that policy's protection — the serving-side view of
the paper's cross-layer stack.

For continuous-batching request scheduling on top of this engine, see
``repro.serve.scheduler``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.parallel import sharding as S
from repro.parallel.ctx import mesh_ctx

LOOPS = ("scan", "python")


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0
    seed: int = 0
    loop: str = "scan"            # "scan" (fused) | "python" (per-token)


@dataclasses.dataclass
class ServeStats:
    """Host-dispatch accounting for the last ``generate`` call.

    ``roundtrips`` counts jitted executable invocations (one host->device
    dispatch + result sync each): the python loop pays 1 prefill + 1 per
    token; the scan loop pays 1 prefill + 1 for the whole generation.
    """
    roundtrips: int = 0
    tokens: int = 0


class Engine:
    def __init__(self, model, params, mesh=None, cfg: ServeConfig | None = None,
                 policy=None, ft_backend: str = "reference", ft_t=None,
                 loop: str | None = None):
        """`policy`: a repro.ft ProtectionPolicy (or registry name) applied to
        every projection.  For ft_backend="pallas" under the jitted serve
        loop, `ft_t` must carry the calibrated truncation LSB(s) — one int or
        a per-site {name: int} table.  Kernels run compiled on the chip and
        interpreted on the CPU backend.  `loop` overrides cfg.loop."""
        from repro.ft import as_policy
        self.model, self.params = model, params
        self.mesh = mesh
        self.cfg = cfg or ServeConfig()
        self.loop = loop or self.cfg.loop
        if self.loop not in LOOPS:
            raise ValueError(f"unknown loop {self.loop!r}; expected {LOOPS}")
        self.policy = as_policy(policy)
        self.ft_backend = ft_backend
        self.ft_t = ft_t
        self.stats = ServeStats()
        self._n_calls = 0
        ctx = S.make_ctx(mesh) if mesh is not None else None
        if mesh is not None:
            # serving layout: TP-sharded weights, replicated over DP (the
            # docstring's claim, applied for real at construction)
            self.params = jax.device_put(
                params, S.param_shardings(params, mesh, no_fsdp=True))

        def _shard_caches(caches):
            if mesh is None or caches is None:
                return caches
            return jax.lax.with_sharding_constraint(
                caches, S.cache_shardings(caches, mesh))

        def _ftc(ftkey):
            if self.policy is None:
                return None
            from repro.models.common import FTCtx
            return FTCtx(self.policy, ftkey, backend=self.ft_backend,
                         t=self.ft_t)

        temperature = self.cfg.temperature

        def _sample(logits, key):
            if temperature <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / temperature, axis=-1).astype(jnp.int32)

        def _prefill(params, batch, max_len, ftkey):
            with mesh_ctx(ctx):
                caches, logits = model.prefill(params, batch, max_len=max_len,
                                               ftc=_ftc(ftkey))
                return _shard_caches(caches), logits

        def _decode(params, caches, token, pos, ftkey):
            with mesh_ctx(ctx):
                return model.decode_step(params, caches, token, pos,
                                         ftc=_ftc(ftkey))

        def _decode_loop(params, caches, tok0, pos0, ftkey, skey, n_new):
            # One executable for the whole generation.  Step i consumes the
            # carried token, decodes it at position pos0+i with the fault
            # stream fold_in(ftkey, i+1) (matching the python loop), folds i
            # into the sampling key, and emits the consumed token — so ys is
            # [tok0, tok1, ..., tok_{n_new-1}], identical to the python path.
            with mesh_ctx(ctx):
                def body(carry, i):
                    caches, tok, key = carry
                    caches, logits = model.decode_step(
                        params, caches, tok, pos0 + i,
                        ftc=_ftc(jax.random.fold_in(ftkey, i + 1)))
                    key = jax.random.fold_in(key, i)
                    nxt = _sample(logits, key)
                    return (caches, nxt, key), tok

                (caches, _, _), toks = jax.lax.scan(
                    body, (caches, tok0, skey),
                    jnp.arange(n_new, dtype=jnp.int32))
            # the final caches are dead to the caller (one generation per
            # loop) but MUST be returned anyway: donated buffers only alias
            # when they line up with an output, so dropping them here turns
            # donate_argnums=(1,) into a silent full-cache copy every call
            # (tools/ftverify FTV105 checks the lowered HLO for this)
            return caches, jnp.moveaxis(toks, 0, 1)  # (B, n_new)

        self._sample = _sample
        self._prefill = jax.jit(_prefill, static_argnums=(2,))
        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._loop = jax.jit(_decode_loop, static_argnums=(6,),
                             donate_argnums=(1,))

    # ------------------------------------------------------------ keys -----
    def _call_key(self, key, seed):
        """Per-call base key.  By default the engine folds the call index
        into the config seed so back-to-back ``generate()`` calls draw fresh
        fault patterns and fresh temperature samples; ``key=``/``seed=``
        pins a call explicitly (replayable reliability accounting)."""
        if key is not None and seed is not None:
            raise ValueError("pass at most one of key= / seed=")
        if key is None:
            key = jax.random.PRNGKey(self.cfg.seed if seed is None else seed)
            if seed is None:
                key = jax.random.fold_in(key, self._n_calls)
        self._n_calls += 1
        ftkey, skey = jax.random.split(jnp.asarray(key))
        return ftkey, skey

    # -------------------------------------------------------- generation ---
    def generate(self, batch, max_new_tokens: int | None = None, *,
                 key=None, seed: int | None = None):
        """batch: model input dict (prompts).  Returns (B, new) tokens.

        ``key``/``seed`` pin this call's fault-draw and sampling streams;
        without them each call folds its index into ``cfg.seed`` (two calls
        never replay the same faults)."""
        n_new = (self.cfg.max_new_tokens if max_new_tokens is None
                 else max_new_tokens)
        prompt_len = batch["tokens"].shape[1]
        if self.model.cfg.frontend == "vision":
            prompt_len += self.model.cfg.n_frontend_tokens
        max_len = prompt_len + n_new
        if self.mesh is not None:
            batch = jax.device_put(batch, S.batch_shardings(batch, self.mesh))
        ftkey, skey = self._call_key(key, seed)
        caches, logits = self._prefill(self.params, batch, max_len, ftkey)
        tok = self._sample(logits, skey)
        if n_new == 0:                       # prefill-only probe
            self.stats = ServeStats(roundtrips=1, tokens=0)
            return jnp.zeros((tok.shape[0], 0), jnp.int32)
        pos0 = jnp.asarray(prompt_len, jnp.int32)
        if self.loop == "scan":
            _, out = self._loop(self.params, caches, tok, pos0, ftkey, skey,
                                n_new)
            self.stats = ServeStats(roundtrips=2, tokens=int(out.size))
            return out
        out = []
        for i in range(n_new):
            out.append(tok)
            caches, logits = self._decode(
                self.params, caches, tok, pos0 + i,
                jax.random.fold_in(ftkey, i + 1))
            skey = jax.random.fold_in(skey, i)
            tok = self._sample(logits, skey)
        out = jnp.stack(out, axis=1)
        self.stats = ServeStats(roundtrips=1 + n_new, tokens=int(out.size))
        return out
