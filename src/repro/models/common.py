"""Shared model components: norms, rotary embeddings, inits, activations."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel import ctx as pctx
from repro.parallel.sharding import tp_split


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------- init -----
def dense_init(key, d_in: int, d_out, dtype, scale: float = 1.0):
    shape = (d_in,) + (tuple(d_out) if isinstance(d_out, (tuple, list))
                       else (d_out,))
    std = scale / (d_in ** 0.5)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    # std 1/sqrt(d): unit-variance logits under a tied unembed; gemma-style
    # input scaling (scale_embeds) restores O(1) activations at the input.
    return (jax.random.truncated_normal(key, -2, 2, (vocab, d), jnp.float32)
            * d ** -0.5).astype(dtype)


# ---------------------------------------------------------------- norms ----
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    # variance statistics in f32, data flow in the compute dtype: keeps the
    # activation (and its cotangent) bf16 so no full-width f32 residual-
    # stream tensors survive into the backward pass
    xf = x.astype(jnp.float32)
    rs = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return x * rs.astype(x.dtype) * (1.0 + scale).astype(x.dtype)


# ---------------------------------------------------------------- rope -----
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding.  x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ activations --
def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ------------------------------------------------------------ ft routing ---
class EmuCtx:
    """Structural-cost emulation of FlexHyCA protection (no RNG): used by the
    perf hillclimb to compare the naive TPU port of the DPPU (a second
    gathered GEMM pass over the important channels, 'two_pass') against the
    fused design (protection in the epilogue of the same tile pass — the
    protected_mm kernel; zero extra GEMM cost, 'fused')."""

    def __init__(self, mode: str, s_th: float = 0.05):
        assert mode in ("two_pass", "fused")
        self.mode = mode
        self.s_th = s_th


class FTCtx:
    """Per-forward fault-tolerance context: a ProtectionPolicy (legacy
    FTConfig and registry names are converted) + per-site importance masks +
    deterministic per-site PRNG keys.  None => clean bf16 math.

    ``backend`` selects the protect_linear implementation per forward:
    "reference" (functional model) or "pallas" (fused TPU kernel).  The
    pallas kernel takes the truncation LSB statically, so under jit supply
    ``t`` — one int for all sites or a per-site {name: int} calibration
    table (repro.ft.calibrate_t).  Kernels run compiled on the chip and
    interpreted on the CPU backend (``repro.kernels.resolve_interpret``).

    ``dyn`` optionally carries traced overrides of the policy's numeric
    protection knobs ({"ib_th": ..., "nb_th": ..., "q_scale": ...}) so a
    vmap axis of candidate designs shares one executable — the batched DSE
    oracle path (reference backend only; see ``repro.core.evaluate``).

    ``key`` may be a single PRNG key (one fault stream for the whole
    forward) or a (B, 2) batch of keys — one *independent* stream per batch
    row, so a serving batch keeps per-request fault accounting: row b's
    draws (and its quantization scales) depend only on row b (reference
    backend, weight_faults=False; see ``repro.serve.scheduler``).

    ``ste=True`` routes every site through ``protect_linear_ste`` — forward
    bit-identical to the faulty datapath, backward the clean-matmul
    straight-through gradient — which is what fault-aware training (FAT)
    threads into the train step (see ``repro.train.train_step`` and
    docs/training.md)."""

    def __init__(self, ft, key, masks=None, protected_layers=None,
                 backend: str = "reference", t=None, dyn=None,
                 ste: bool = False):
        from repro.ft import as_policy
        self.ft = as_policy(ft)
        self.key = key
        self.masks = masks or {}
        self.protected_layers = protected_layers  # set of layer names (arch/alg)
        self.backend = backend
        self.t = t
        self.dyn = dyn
        self.ste = ste

    def site_key(self, name: str):
        import zlib
        c = zlib.crc32(name.encode())
        if getattr(self.key, "ndim", 1) == 2:      # (B, 2) per-row streams
            return jax.vmap(lambda k: jax.random.fold_in(k, c))(self.key)
        return jax.random.fold_in(self.key, c)

    def site_t(self, name: str):
        return self.t.get(name) if isinstance(self.t, dict) else self.t


def linear(x: jax.Array, w: jax.Array, b=None, *,
           ftc: FTCtx | None = None, name: str = "") -> jax.Array:
    """Every projection in the zoo routes through here — the integration point
    of the paper's technique (ft_linear) with the LM stack.  Its ops carry
    the ``linear`` named scope (the protected datapath ``linear/protect``),
    which a profiler trace's HLO keeps.  ``name`` keys the site's fault
    stream and importance mask, and its last part (the weight's key) says
    how a mesh divides ``w`` (``parallel.sharding.tp_split``)."""
    with jax.named_scope("linear"):
        return _linear(x, w, b, ftc=ftc, name=name)


def _linear(x, w, b, *, ftc, name):
    if isinstance(ftc, EmuCtx):
        w2 = w.reshape(w.shape[0], -1)
        y = x @ w2
        if ftc.mode == "two_pass":
            # DPPU as a separate pass: recompute the important channels from
            # a second weight read and vote (naive port of the paper's arch)
            k = max(int(ftc.s_th * w2.shape[1]), 1)
            y_sel = x @ w2[:, :k]
            y = jnp.concatenate(
                [((y[..., :k] + y_sel) * 0.5).astype(y.dtype), y[..., k:]],
                axis=-1)
        y = y.reshape(*x.shape[:-1], *w.shape[1:])
    elif ftc is None or ftc.ft is None:
        y = x @ w.reshape(w.shape[0], -1)
        y = y.reshape(*x.shape[:-1], *w.shape[1:])
    else:
        from repro.ft import protect_linear, protect_linear_ste
        pl = protect_linear_ste if ftc.ste else protect_linear
        w2 = w.reshape(w.shape[0], -1).astype(jnp.float32)
        imp = ftc.masks.get(name)
        prot = (ftc.protected_layers is None
                or name.split("/")[0] in ftc.protected_layers)
        sk = ftc.site_key(name)
        if getattr(sk, "ndim", 1) == 2:
            # batched per-row streams: the FTCtx carries one key per batch
            # row; x flattens to (B*S, K) row-major, so each row-key repeats
            # over that row's S positions.
            reps = max(x.size // x.shape[-1], 1) // sk.shape[0]
            if reps != 1:
                sk = jnp.repeat(sk, reps, axis=0)
        with jax.named_scope("protect"):
            y = pl(sk,
                   x.astype(jnp.float32).reshape(-1, w.shape[0]),
                   w2, ftc.ft,
                   important=None if imp is None else jnp.asarray(imp),
                   layer_protected=prot, backend=ftc.backend,
                   t=ftc.site_t(name), dyn=ftc.dyn, split=tp_split(name))
        y = y.reshape(*x.shape[:-1], *w.shape[1:]).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def tag(probe, name: str, x: jax.Array) -> jax.Array:
    """Neuron-importance tap site (Algorithm 1)."""
    return x if probe is None else probe.tag(name, x)


ac = pctx.ac  # re-export: activation sharding constraint
