"""Continuous-batching scheduler: eviction, bucket reuse, per-request
fault-stream independence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ft
from repro.configs import get_config
from repro.models import build
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler, SchedulerConfig


@pytest.fixture(scope="module")
def danube():
    cfg = get_config("h2o-danube-1.8b", reduced=True)
    m = build(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _prompt(n, vocab, seed):
    return [int(t) for t in jax.random.randint(jax.random.PRNGKey(seed),
                                               (n,), 0, vocab)]


def test_scheduler_matches_engine_greedy(danube):
    """A lone request through the bucketed scheduler (padded prefill,
    per-row positions, batch slots mostly idle) must emit exactly what the
    engine emits for the same prompt — bucketing is a pure optimization."""
    cfg, m, params = danube
    prompt = _prompt(6, cfg.vocab, seed=1)
    sched = Scheduler(m, params, SchedulerConfig(
        max_batch=3, buckets=(8,), max_new_tokens=10, decode_chunk=4))
    out = sched.run([Request(rid=0, tokens=prompt, max_new_tokens=10)])
    eng = Engine(m, params, cfg=ServeConfig(max_new_tokens=10))
    ref = np.asarray(eng.generate(
        {"tokens": jnp.asarray([prompt], jnp.int32)}))[0]
    assert out[0].generated == [int(t) for t in ref]
    assert out[0].finish_reason == "length"


def test_eos_and_length_eviction_reuse_slots(danube):
    """More requests than slots: every request completes; EOS truncates at
    the EOS token; the freed slot serves the queue."""
    cfg, m, params = danube
    mk = lambda: [Request(rid=i, tokens=_prompt(4 + i % 3, cfg.vocab, i),
                          max_new_tokens=6 + (i % 2)) for i in range(5)]
    sched = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=8, decode_chunk=3))
    probe = sched.run(mk())
    assert set(probe) == set(range(5))
    assert all(r.finish_reason == "length" for r in probe.values())
    assert all(len(r.generated) == 6 + (i % 2) for i, r in probe.items())
    # pick a token some request emits mid-stream and declare it EOS
    rid, toks = 0, probe[0].generated
    eos = toks[2]
    first = toks.index(eos)
    sched2 = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=8, decode_chunk=3,
        eos_id=eos))
    done = sched2.run(mk())
    assert set(done) == set(range(5))
    assert done[rid].finish_reason == "eos"
    assert done[rid].generated == toks[:first + 1]       # truncated at EOS
    assert done[rid].generated[-1] == eos


def test_bucket_reuse_bounds_recompiles(danube):
    """Prompt lengths 3/5/7/11 under buckets (8, 16): exactly one prefill
    executable per *bucket* (not per length), one chunk executable."""
    cfg, m, params = danube
    sched = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8, 16), max_new_tokens=4, decode_chunk=2))
    reqs = [Request(rid=i, tokens=_prompt(n, cfg.vocab, i), max_new_tokens=4)
            for i, n in enumerate((3, 5, 7, 11))]
    out = sched.run(reqs)
    assert all(len(r.generated) == 4 for r in out.values())
    assert sched._prefill_one._cache_size() == 2         # one per bucket
    assert sched._chunk._cache_size() == 1
    assert sched._insert._cache_size() == 1
    # longer prompts than any bucket are rejected, not silently truncated
    with pytest.raises(ValueError):
        sched.run([Request(rid=9, tokens=_prompt(20, cfg.vocab, 9))])


def test_per_request_fault_stream_independence(danube):
    """Under a protection policy with faults, a request's generation is a
    pure function of (request id, its own tokens): serving it alone or
    beside other traffic yields bit-identical tokens, so reliability
    accounting stays per-request."""
    cfg, m, params = danube
    # ber high enough that some flip lands an argmax change within 8 tokens
    # on any key stream (the partitionable-threefry stream at 3e-3 happens
    # to leave this short generation clean)
    policy = ft.get_policy("crt1", ber=1e-2, weight_faults=False)
    scfg = SchedulerConfig(max_batch=3, buckets=(8,), max_new_tokens=8,
                           decode_chunk=4)
    a_alone = Scheduler(m, params, scfg, policy=policy).run(
        [Request(rid=7, tokens=_prompt(5, cfg.vocab, 7), max_new_tokens=8)])
    crowd = [Request(rid=7, tokens=_prompt(5, cfg.vocab, 7),
                     max_new_tokens=8),
             Request(rid=8, tokens=_prompt(3, cfg.vocab, 8),
                     max_new_tokens=8),
             Request(rid=9, tokens=_prompt(7, cfg.vocab, 9),
                     max_new_tokens=8)]
    a_crowded = Scheduler(m, params, scfg, policy=policy).run(crowd)
    assert a_alone[7].generated == a_crowded[7].generated
    # faults are real: the protected stream differs from the clean one
    clean = Scheduler(m, params, scfg).run(
        [Request(rid=7, tokens=_prompt(5, cfg.vocab, 7), max_new_tokens=8)])
    assert clean[7].generated != a_alone[7].generated


def test_scheduler_guards(danube):
    cfg, m, params = danube
    # sliding-window models: buckets must fit inside the window
    with pytest.raises(ValueError, match="window"):
        Scheduler(m, params, SchedulerConfig(buckets=(8, 64)))
    # recurrent state would integrate pad tokens under *bucketed* prefill
    ssm_cfg = get_config("mamba2-2.7b", reduced=True)
    ssm = build(ssm_cfg)
    with pytest.raises(ValueError, match="attention"):
        Scheduler(ssm, ssm.init(jax.random.PRNGKey(0)))
    # exact-length prefill needs an explicit capacity bound
    with pytest.raises(ValueError, match="max_prompt"):
        Scheduler(m, params, SchedulerConfig(buckets=None))
    with pytest.raises(ValueError, match="kv layout"):
        Scheduler(m, params, SchedulerConfig(kv="sparse"))
    # the pallas backend takes one global key + static t: no per-request
    # streams (reference and fused both work — see the serving tests)
    with pytest.raises(ValueError, match="pallas"):
        Scheduler(m, params, policy=ft.get_policy("crt1", ber=1e-3),
                  ft_backend="pallas")
    # fail-fast request validation: duplicate rids (results and fault
    # streams are keyed by rid) and per-request caps beyond slot capacity
    sched = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=4))
    with pytest.raises(ValueError, match="duplicate"):
        sched.run([Request(rid=1, tokens=_prompt(4, cfg.vocab, 0),
                           max_new_tokens=4),
                   Request(rid=1, tokens=_prompt(4, cfg.vocab, 1),
                           max_new_tokens=4)])
    with pytest.raises(ValueError, match="capacity"):
        sched.run([Request(rid=1, tokens=_prompt(4, cfg.vocab, 0),
                           max_new_tokens=9)])
    # a single request can never need more KV blocks than the pool holds
    tiny = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=4, block_size=2,
        n_blocks=3))
    with pytest.raises(ValueError, match="blocks"):
        tiny.run([Request(rid=1, tokens=_prompt(8, cfg.vocab, 0),
                          max_new_tokens=4)])


# the paged pool in the python-loop layers, and in place in the layer
# scan's carry: 3 scanned sliding-window layers whose window of 8 the
# requests' positions (up to 14) wrap, and scanned super-blocks of a global
# and a local layer, whose two pools differ in table width
PAGED_CASES = {
    "unrolled": {},
    "scanned_wrap": {"unroll": False, "n_layers": 3, "window": 8},
    "scanned_global_local": {"unroll": False, "n_layers": 4, "window": 8,
                             "block_pattern": ("G", "L")},
}


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_matches_dense(danube, case):
    """The paged KV cache is a pure layout change: the same workload through
    kv='paged' and kv='dense' yields bit-identical tokens, even with a
    deliberately tight block pool that forces requests to wait for blocks."""
    cfg, m, params = danube
    if PAGED_CASES[case]:
        cfg = dataclasses.replace(cfg, **PAGED_CASES[case])
        m = build(cfg)
        params = m.init(jax.random.PRNGKey(0))
    mk = lambda: [Request(rid=i, tokens=_prompt(3 + 2 * (i % 3), cfg.vocab,
                                                20 + i),
                          max_new_tokens=5 + (i % 2)) for i in range(5)]
    outs = {}
    for kv in ("dense", "paged"):
        scfg = SchedulerConfig(max_batch=2, buckets=(8,), max_new_tokens=6,
                               decode_chunk=3, kv=kv)
        outs[kv] = Scheduler(m, params, scfg).run(mk())
    for i in range(5):
        assert outs["paged"][i].generated == outs["dense"][i].generated
    # tight pool: room for roughly one request's blocks at a time
    probe = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=6, kv="paged",
        block_size=4))
    need1 = probe._blocks_needed(8, 6)
    tight = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=6, decode_chunk=3,
        kv="paged", block_size=4, n_blocks=1 + need1 + 1))
    out_t = tight.run(mk())
    for i in range(5):
        assert out_t[i].generated == outs["dense"][i].generated
    assert tight.stats.blocks_in_use_peak <= need1 + 1


def test_weight_faults_serving(danube):
    """PR 3's weight_faults=False restriction is lifted: per-row weight
    flip streams give each request its own faulty view of the shared SRAM.
    Tokens stay a pure function of rid (alone == crowded), and the fused
    backend reproduces the reference stream bit-for-bit."""
    cfg, m, params = danube
    policy = ft.get_policy("crt1", ber=3e-3, weight_faults=True)
    scfg = SchedulerConfig(max_batch=2, buckets=(8,), max_new_tokens=6,
                           decode_chunk=3)
    alone = Scheduler(m, params, scfg, policy=policy).run(
        [Request(rid=7, tokens=_prompt(5, cfg.vocab, 7), max_new_tokens=6)])
    crowd = [Request(rid=7, tokens=_prompt(5, cfg.vocab, 7),
                     max_new_tokens=6),
             Request(rid=8, tokens=_prompt(3, cfg.vocab, 8),
                     max_new_tokens=6)]
    crowded = Scheduler(m, params, scfg, policy=policy).run(crowd)
    assert alone[7].generated == crowded[7].generated
    fused = Scheduler(m, params, scfg, policy=policy,
                      ft_backend="fused").run(
        [Request(rid=7, tokens=_prompt(5, cfg.vocab, 7), max_new_tokens=6)])
    assert fused[7].generated == alone[7].generated


def test_exact_mode_recurrent_and_enc_dec():
    """buckets=None (exact-length prefill) + paged KV admits the families
    bucketed prefill rejects: recurrent/SSM state and encoder-decoder
    cross-attention, with per-slot encoder lengths."""
    ssm_cfg = get_config("mamba2-2.7b", reduced=True)
    sm = build(ssm_cfg)
    sparams = sm.init(jax.random.PRNGKey(0))
    scfg = SchedulerConfig(max_batch=2, buckets=None, max_prompt=8,
                           max_new_tokens=5, decode_chunk=2)
    mk = lambda: [Request(rid=i, tokens=_prompt(4 + 2 * (i % 2),
                                                ssm_cfg.vocab, i),
                          max_new_tokens=5) for i in range(3)]
    crowded = Scheduler(sm, sparams, scfg).run(mk())
    assert all(len(r.generated) == 5 for r in crowded.values())
    alone = Scheduler(sm, sparams, scfg).run([mk()[0]])
    assert alone[0].generated == crowded[0].generated

    ed_cfg = get_config("seamless-m4t-medium", reduced=True)
    em = build(ed_cfg)
    eparams = em.init(jax.random.PRNGKey(0))
    frames = lambda n, s: jax.random.normal(
        jax.random.PRNGKey(90 + s), (n, ed_cfg.d_model), jnp.float32)
    ereqs = lambda: [Request(rid=i, tokens=_prompt(4, ed_cfg.vocab, 40 + i),
                             max_new_tokens=4,
                             extras={"frames": frames(5 + i, i)})
                     for i in range(3)]
    ecrowd = Scheduler(em, eparams, scfg).run(ereqs())
    assert all(len(r.generated) == 4 for r in ecrowd.values())
    ealone = Scheduler(em, eparams, scfg).run([ereqs()[1]])
    assert ealone[1].generated == ecrowd[1].generated


def test_recurrent_paged_matches_dense():
    """kv='dense' is legal for recurrent families too (their R/S state rows
    are dense per-slot either way), which restores the bit-exactness oracle:
    the same workload through kv='paged' and kv='dense' must emit identical
    tokens for a config that mixes attention and recurrent blocks."""
    cfg = get_config("recurrentgemma-9b", reduced=True)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    scfg = lambda kv: SchedulerConfig(max_batch=2, buckets=None, max_prompt=6,
                                      max_new_tokens=4, decode_chunk=2, kv=kv)
    mk = lambda: [Request(rid=i, tokens=_prompt(4 + (i % 2), cfg.vocab,
                                                60 + i), max_new_tokens=4)
                  for i in range(3)]
    outs = {kv: Scheduler(m, params, scfg(kv)).run(mk())
            for kv in ("dense", "paged")}
    for i in range(3):
        assert outs["paged"][i].generated == outs["dense"][i].generated


def test_scheduler_vision_frontend():
    cfg = get_config("paligemma-3b", reduced=True)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    reqs = [Request(rid=i, tokens=_prompt(4 + i, cfg.vocab, i),
                    max_new_tokens=5,
                    extras={"patch_embeds": jax.random.normal(
                        jax.random.PRNGKey(50 + i),
                        (cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)})
            for i in range(3)]
    sched = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=5, decode_chunk=2))
    out = sched.run(reqs)
    assert all(len(r.generated) == 5 for r in out.values())
