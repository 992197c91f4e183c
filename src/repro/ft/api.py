"""``protect_linear`` — the single fault-tolerant linear entry point.

Two backends compute the same FlexHyCA semantics:

  * ``backend="reference"`` — the bit-exact functional model (the former
    ``repro.core.flexhyca.ft_linear`` math), jitted with the policy's
    structure static and its BER traced, so BER sweeps vmap/scan over one
    executable.
  * ``backend="pallas"`` — the fused TPU kernel
    (``repro.kernels.protected_mm``): int8 MXU matmul, 24-bit saturating
    accumulate, Q_scale-constrained truncation and selective bit protection
    in the epilogue of the same tile pass.  The truncation LSB ``t`` is
    per-layer deployment state on the DLA; it is calibrated from the inputs
    when not supplied, so this backend needs concrete (non-traced) operands.
    The kernel models ECC-protected weight SRAM, so ``policy.weight_faults``
    does not apply on this path.

  * ``backend="fused"`` — the fused decode kernel
    (``repro.kernels.fused_decode``): the *same* key schedule and fault
    draws as the reference backend, packed into int32 flip words and
    consumed by one Pallas pass (matmul + saturate + in-kernel truncation
    LSB + XOR + DPPU select).  Bit-identical to ``reference`` for every
    registry policy — global or (M, 2) per-row keys, weight faults
    included (per-row weight faults give each batch row an independent
    faulty-weight view), traced ``dyn`` overrides supported.  This is the
    serving hot-path backend; see ``docs/kernels.md``.

Reference and fused agree bit-exactly at any BER; pallas agrees at BER 0
and draws from an independent RNG stream otherwise (it uses pre-generated
uint32 planes rather than the packed flip words).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core import quantization as Q
from repro.ft.policy import ProtectionPolicy
from repro.parallel import ctx as pctx

BACKENDS = ("reference", "fused", "pallas")


def calibrate_t(x, w, q_scale: int = 0) -> int:
    """Pick a layer's truncation LSB from calibration data — deployment
    state for the pallas backend (whose kernel takes ``t`` statically)."""
    from repro.kernels.protected_mm.ops import calibrate_t as _calibrate
    return _calibrate(x, w, q_scale=q_scale)


def protect_linear(key: jax.Array, x: jax.Array, w: jax.Array,
                   policy: ProtectionPolicy,
                   important: jax.Array | None = None, *,
                   layer_protected: bool = True,
                   backend: str = "reference",
                   t: int | None = None,
                   dyn=None, split: str | None = None) -> jax.Array:
    """Fault-tolerant linear: float in/out, faulty quantized DLA inside.

    Args:
      key: one PRNG key, or an (M, 2) batch of keys — one per row of the
        flattened x — for *per-row* independent fault streams (and per-row
        quantization scales), so a serving batch's reliability accounting
        stays per-request.  Per-row mode is supported by the reference and
        fused backends; with ``policy.weight_faults`` each row additionally
        sees its own independently drawn faulty-weight view.
      x: (..., K) activations.  w: (K, N) weights.
      policy: a :class:`ProtectionPolicy` (see ``repro.ft.get_policy``).
      important: (N,) bool mask of important output channels (Algorithm 1);
        consumed only by recompute policies.
      layer_protected: for whole-layer-TMR policies (arch/alg) — whether this
        layer is in the protected (sensitive) set.
      backend: "reference" | "fused" | "pallas".
      t: truncation LSB for the pallas backend (calibrated from x/w if None).
      dyn: optional mapping of *traced* overrides for the policy's numeric
        protection knobs (``ib_th`` / ``nb_th`` / ``q_scale``).  The static
        values in ``policy`` are metadata the executable specializes on; a
        ``dyn`` entry moves that knob onto the trace so a batch of candidate
        designs with different knob values shares one compiled executable
        (the batched DSE oracle — see ``repro.core.evaluate``).  Supported
        by the reference and fused backends (the fused kernel takes
        ``q_scale`` as a scalar operand and folds ``ib_th``/``nb_th`` into
        the flip-word draws).
      split: how the serving layout divides ``w`` over the tensor-parallel
        axis — "column" (its N), "row" (its K) or None — which the fused
        backend needs under a mesh (``parallel.ctx``) whose axis holds more
        than one device: there it runs the kernel in a ``shard_map`` with
        its exchange (``kernels/fused_decode/ops.py``).  The reference
        backend is partitioned by GSPMD and ignores it.
    Returns (..., N) float32.
    """
    if backend == "reference":
        return _protect_reference(key, x, w, policy, important,
                                  layer_protected, dyn)
    if backend == "fused":
        from repro.kernels.fused_decode.ops import fused_protect_linear
        return fused_protect_linear(key, x, w, policy, important,
                                    layer_protected=layer_protected,
                                    dyn=dyn, tp=_tp(split))
    if getattr(key, "ndim", 1) == 2:
        raise ValueError("per-row key batches are only supported by "
                         "backend='reference' or backend='fused'")
    if dyn:
        raise ValueError("dyn knob overrides are only supported by "
                         "backend='reference' or backend='fused' (the "
                         "pallas kernel takes its protection knobs "
                         "statically)")
    if backend == "pallas":
        return _protect_pallas(key, x, w, policy, important,
                               layer_protected=layer_protected, t=t)
    raise ValueError(f"unknown backend {backend!r}; expected one of "
                     f"{BACKENDS}")


def _tp(split: str | None):
    """``(mesh, axis, split)`` of the mesh context whose tensor-parallel
    axis holds more than one device; None without one."""
    c = pctx.get_ctx()
    if c is None or c.tp_size == 1:
        return None
    return c.mesh, c.tp, split


# ------------------------------------------------------ straight-through ----
@jax.custom_vjp
def _ste_tie(x, w, y_prot):
    """Forward: the protected output, untouched.  Backward: cotangents of the
    clean float matmul ``x @ w`` — the straight-through estimator."""
    return y_prot


def _ste_fwd(x, w, y_prot):
    return y_prot, (x, w)


def _ste_bwd(res, g):
    x, w = res
    g2 = g.astype(jnp.float32).reshape(-1, w.shape[1])
    x2 = x.astype(jnp.float32).reshape(-1, w.shape[0])
    gx = (g2 @ w.astype(jnp.float32).T).reshape(x.shape).astype(x.dtype)
    gw = (x2.T @ g2).astype(w.dtype)
    return gx, gw, jnp.zeros_like(g)


_ste_tie.defvjp(_ste_fwd, _ste_bwd)


def protect_linear_ste(key: jax.Array, x: jax.Array, w: jax.Array,
                       policy: ProtectionPolicy,
                       important: jax.Array | None = None, **kw) -> jax.Array:
    """:func:`protect_linear` with a straight-through gradient rule — the
    fault-aware-training (FAT) entry point.

    The forward value is the :func:`protect_linear` output *unchanged* (the
    integer inject/protect/quantize datapath stays bit-exact — the training
    loss sees exactly the faulty DLA the deployment will run), while the
    backward pass returns the cotangents of the clean float ``x @ w``: the
    non-differentiable quantize/flip/truncate chain is treated as identity,
    so gradients flow and the network learns to place its decision margins
    where bit flips cannot reach them.  ``kw`` is forwarded verbatim
    (``layer_protected`` / ``backend`` / ``t`` / ``dyn`` / ``split``).
    """
    y = protect_linear(key, jax.lax.stop_gradient(x),
                       jax.lax.stop_gradient(w), policy, important, **kw)
    return _ste_tie(x, w, y)


# ------------------------------------------------------------ reference ----
@partial(jax.jit, static_argnames=("layer_protected",))
def _protect_reference(key, x, w, policy: ProtectionPolicy, important,
                       layer_protected: bool, dyn=None):
    """The former ``ft_linear`` datapath, structure-dispatched on the policy.

    Every fault-injection site executes unconditionally with the (possibly
    traced) BER — at BER 0 each injection is the identity, so the output is
    bit-identical to the branch-skipping legacy code while remaining
    vmap-able over a BER axis.  ``dyn`` optionally replaces the static
    ``ib_th`` / ``nb_th`` / ``q_scale`` metadata with traced values so those
    knobs can ride the same vmap axis (integer datapath => the result stays
    bit-identical to the static trace of the same values).

    An (M, 2) ``key`` batch switches to *per-row* mode: each row gets its
    own activation-quantization scale, truncation LSB and fault draws, so
    row b's output is a function of row b's input and key only — batch
    composition cannot perturb another request's fault stream (the
    continuous-batching scheduler's reliability contract).  With
    ``policy.weight_faults`` that extends to the weights: each row sees the
    shared weight matrix through its own independently drawn flip words, as
    if the DLA re-read a freshly faulted weight SRAM per request.
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    per_row = getattr(key, "ndim", 1) == 2
    if per_row:
        ks = jax.vmap(lambda k: jax.random.split(k, 3))(key)   # (M, 3, 2)
        kw, ka, kd = ks[:, 0], ks[:, 1], ks[:, 2]
    else:
        kw, ka, kd = jax.random.split(key, 3)
    n = w.shape[1]
    alg, arch, circ = policy.algorithm, policy.arch, policy.circuit
    dyn = dyn or {}
    ib_th = dyn.get("ib_th", circ.ib_th)
    nb_th = dyn.get("nb_th", circ.nb_th)
    q_scale = dyn.get("q_scale", alg.q_scale)

    xq, sx = Q.quantize(x2, axis=1 if per_row else None)
    wq, sw = Q.quantize(w)
    if policy.weight_faults and per_row:
        # each row's private faulty-weight view: (M, 2) kw keys -> (M, K, N)
        # packed flip words applied to the shared weights
        wfl = jax.vmap(lambda k: faults.flip_word(
            k, wq.shape, policy.ber, Q.OUT_BITS))(kw)
        uw = (wq[None, :, :] & ((1 << Q.OUT_BITS) - 1)) ^ wfl
        wq_f = jnp.where((uw & (1 << (Q.OUT_BITS - 1))) != 0,
                         uw - (1 << Q.OUT_BITS), uw)
        acc = jax.vmap(lambda a, b: jnp.matmul(
            a, b, preferred_element_type=jnp.int32))(xq, wq_f)
    else:
        wq_f = (faults.inject_weight_faults(kw, wq, policy.ber)
                if policy.weight_faults else wq)
        acc = jnp.matmul(xq, wq_f, preferred_element_type=jnp.int32)
    acc = Q.saturate(acc)
    absmax = (jnp.max(jnp.abs(acc), axis=1, keepdims=True) if per_row
              else jnp.max(jnp.abs(acc)))
    t = Q.choose_trunc_lsb(absmax, q_scale=q_scale)
    yq = Q.truncate_acc(acc, t)

    def inject(keys, yq, protect):
        if per_row:   # independent per-row draws: (M, 2) keys over (M, N)
            return jax.vmap(lambda k, y: faults.inject_output_faults(
                k, y, policy.ber, protect_top=protect))(keys, yq)
        return faults.inject_output_faults(keys, yq, policy.ber,
                                           protect_top=protect)

    # circuit layer: per-channel protected high bits
    imp = jnp.zeros((n,), bool) if important is None else important
    protect = jnp.where(imp, ib_th, nb_th).astype(jnp.int32)
    if arch.whole_layer_tmr and layer_protected:
        # spatial/temporal TMR of the whole layer: every bit voted
        protect = jnp.full((n,), Q.OUT_BITS, jnp.int32)
    yq_f = inject(ka, yq, protect)

    if arch.recompute and important is not None:
        # architecture layer: DPPU recomputes important channels on its own
        # (clean weight SRAM + IB_TH-bit-protected MACs) and overrides.
        acc_d = Q.saturate(jnp.matmul(xq, wq,
                                      preferred_element_type=jnp.int32))
        yq_d = Q.truncate_acc(acc_d, t)
        yq_d = inject(kd, yq_d,
                      jnp.broadcast_to(jnp.asarray(ib_th, jnp.int32), (n,)))
        yq_f = jnp.where(important[None, :], yq_d, yq_f)

    scale = sx * sw * (2.0 ** t.astype(jnp.float32))
    y = yq_f.astype(jnp.float32) * scale
    return y.reshape(*orig_shape[:-1], n)


# --------------------------------------------------------------- pallas ----
def _pad_to(a: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, -s % m) for s, m in zip(a.shape, mults)]
    if any(p for _, p in pads):
        a = jnp.pad(a, pads)
    return a


def _protect_pallas(key, x, w, policy: ProtectionPolicy, important, *,
                    layer_protected: bool, t: int | None, block: int = 128):
    from repro.kernels.fault_inject.ops import random_planes
    from repro.kernels.protected_mm.kernel import protected_mm

    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]).astype(jnp.float32)
    w = w.astype(jnp.float32)
    n = w.shape[1]

    xq, sx = Q.quantize(x2)
    wq, sw = Q.quantize(w)
    if t is None:
        if isinstance(x, jax.core.Tracer) or isinstance(w, jax.core.Tracer):
            raise ValueError(
                "backend='pallas' under jit/vmap needs a pre-calibrated "
                "truncation LSB: pass protect_linear(..., t=...) (see "
                "repro.ft.calibrate_t) or use backend='reference'")
        acc = Q.saturate(jnp.matmul(xq, wq,
                                    preferred_element_type=jnp.int32))
        t = int(Q.choose_trunc_lsb(jnp.max(jnp.abs(acc)),
                                   q_scale=policy.algorithm.q_scale))

    circ = policy.circuit
    if policy.arch.whole_layer_tmr:
        ib = nb = Q.OUT_BITS if layer_protected else 0
    else:
        ib, nb = circ.ib_th, circ.nb_th
    if important is None or not policy.uses_importance:
        imp = jnp.zeros((n,), jnp.int32)
    else:
        imp = important.astype(jnp.int32)

    # tile-align all operands (zero padding is exact for the int matmul and
    # sliced away before the rescale)
    xq8 = _pad_to(xq.astype(jnp.int8), (block, block))
    wq8 = _pad_to(wq.astype(jnp.int8), (block, block))
    imp_p = _pad_to(imp, (block,))
    mp, np_ = xq8.shape[0], wq8.shape[1]
    k1, k2 = jax.random.split(key)
    rnd_o = random_planes(k1, (mp, np_))
    rnd_i = random_planes(k2, (mp, np_))

    yq = protected_mm(xq8, wq8, rnd_o, rnd_i, imp_p, t=t,
                      ber=float(policy.ber), ib=ib, nb=nb,
                      bm=block, bn=block, bk=block)
    scale = sx * sw * (2.0 ** t)
    y = yq[:x2.shape[0], :n].astype(jnp.float32) * scale
    return y.reshape(*orig_shape[:-1], n)
