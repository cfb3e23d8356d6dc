"""graft-serve: paged-KV serving equivalence + scheduler contracts.

The load-bearing guarantee: the paged-cache engine reproduces the
contiguous-cache ``generate()`` token-for-token — greedy AND seeded
sampling (``rng_fold="position"``) — on GPT-2-tiny and llama-tiny,
single-chip and TP-sharded. Everything else (admission control, block
recycling, in-flight insertion isolation, preemption, continuous-vs-
static throughput) is the scheduler keeping that guarantee under load.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_example_tpu.serving import (
    BlockAllocator,
    InferenceEngine,
    PagedCacheConfig,
    Request,
    Scheduler,
)
from distributed_pytorch_example_tpu.train.generate import generate

GPT2_KW = dict(vocab_size=97, max_len=64, model_dim=32, num_layers=2,
               num_heads=4, mlp_dim=64)
LLAMA_KW = dict(vocab_size=97, max_len=64, model_dim=32, num_layers=2,
                num_heads=4, num_kv_heads=2, mlp_dim=64)
PAGED = dict(paged_num_blocks=32, paged_block_size=4, paged_max_blocks=8)

_CACHE = {}


def _family(family):
    """(decode_model, paged_model, params) per family, built once."""
    if family not in _CACHE:
        if family == "gpt2":
            from distributed_pytorch_example_tpu.models.gpt2 import GPT2 as M

            kw = GPT2_KW
        else:
            from distributed_pytorch_example_tpu.models.llama import (
                Llama as M,
            )

            kw = LLAMA_KW
        params = M(**kw).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        _CACHE[family] = (
            M(**kw, decode=True), M(**kw, decode=True, **PAGED), params
        )
    return _CACHE[family]


def _prompts(lengths, vocab=97, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lengths]


def _requests(prompts, max_new=8, **kw):
    return [
        Request(rid=f"r{i}", prompt=[int(t) for t in p],
                max_new_tokens=max_new, seed=i, **kw)
        for i, p in enumerate(prompts)
    ]


def _refs(decode_model, params, prompts, max_new=8, **gen_kw):
    """Per-request contiguous-cache generate() outputs (B=1 each, the
    engine's per-request rng contract)."""
    out = []
    for i, p in enumerate(prompts):
        full = generate(
            decode_model, params, jnp.asarray(p)[None], max_new,
            rng=jax.random.key(i), rng_fold="position", **gen_kw,
        )
        out.append(list(np.asarray(full)[0, len(p):]))
    return out


class VirtualClock:
    """Deterministic injectable clock: each read ticks a little (simulated
    work), sleep() jumps. Keeps scheduler tests wall-clock-free."""

    def __init__(self, tick=1e-3):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)


# ---------------------------------------------------------------------------
# equivalence: paged decode == contiguous generate(), token for token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_paged_greedy_matches_generate(family):
    decode_model, paged_model, params = _family(family)
    prompts = _prompts((8, 5, 11))
    refs = _refs(decode_model, params, prompts, temperature=0.0)
    engine = InferenceEngine(
        paged_model, params, num_slots=2, temperature=0.0
    )
    report = engine.run(_requests(prompts))
    for i in range(len(prompts)):
        r = report["results"][f"r{i}"]
        assert r["status"] == "done"
        assert r["tokens"] == refs[i]
    assert report["metrics"]["completed"] == len(prompts)
    # continuous batching actually happened: 3 requests over 2 slots
    assert report["metrics"]["admitted"] == 3


@pytest.mark.parametrize(
    "family,sample_kw",
    [("gpt2", dict(temperature=1.0, top_k=5)),
     ("llama", dict(temperature=1.0, top_p=0.9))],
    ids=["gpt2-topk", "llama-topp"],
)
def test_paged_seeded_sampling_matches_generate(family, sample_kw):
    """Seeded sampling is EXACT, not distributional: the engine's
    position-folded per-request keys (serving/sampling.py) reproduce
    generate(rng_fold="position") bit-for-bit."""
    decode_model, paged_model, params = _family(family)
    prompts = _prompts((8, 5, 11), seed=1)
    refs = _refs(decode_model, params, prompts, **sample_kw)
    engine = InferenceEngine(
        paged_model, params, num_slots=2, **sample_kw
    )
    report = engine.run(_requests(prompts))
    for i in range(len(prompts)):
        assert report["results"][f"r{i}"]["tokens"] == refs[i]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_paged_sharded_tensor2_matches_generate(devices, family):
    """TP-trained checkpoints serve without gathering: the engine under a
    tensor=2 mesh (pool kv-heads TP-sharded, blocks over data axes)
    stays token-exact vs the dense single-logical-device generate()."""
    from distributed_pytorch_example_tpu.parallel.partition import (
        transformer_partitioner,
    )
    from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh

    decode_model, paged_model, params = _family(family)
    prompts = _prompts((8, 6, 10), seed=2)
    refs = _refs(decode_model, params, prompts, temperature=0.0)
    mesh = make_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    engine = InferenceEngine(
        paged_model, params, num_slots=2, temperature=0.0,
        partitioner=transformer_partitioner(mesh),
    )
    report = engine.run(_requests(prompts))
    for i in range(len(prompts)):
        assert report["results"][f"r{i}"]["tokens"] == refs[i]


def test_eos_and_rejection():
    decode_model, paged_model, params = _family("gpt2")
    prompts = _prompts((6,))
    # find the greedy continuation's second token and use it as EOS: the
    # request must stop there (EOS included) instead of running to max
    ref = _refs(decode_model, params, prompts, temperature=0.0,
                max_new=8)[0]
    eos = ref[2]
    engine = InferenceEngine(
        paged_model, params, num_slots=2, temperature=0.0
    )
    reqs = _requests(prompts, max_new=8, eos_id=int(eos))
    # plus one request that can NEVER fit (prompt+new > max context 32)
    reqs.append(Request(rid="huge", prompt=[1] * 30, max_new_tokens=20))
    report = engine.run(reqs)
    done = report["results"]["r0"]
    stop = done["tokens"].index(int(eos))
    assert done["tokens"] == ref[:stop + 1]
    assert report["results"]["huge"]["status"] == "rejected"
    assert report["metrics"]["rejected"] == 1


def test_engine_preemption_restart_bit_identical():
    """Pool pressure mid-decode: the youngest resident is preempted,
    requeued, and — because the rng folds absolute positions — its
    restarted stream reproduces the exact same tokens."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    _, _, params = _family("gpt2")
    # 11 allocatable blocks; two requests that each grow to 7 blocks
    # (8 prompt + 20 new = 28 tokens) cannot coexist at full length
    model = GPT2(**GPT2_KW, decode=True, paged_num_blocks=12,
                 paged_block_size=4, paged_max_blocks=8)
    decode_model, _, _ = _family("gpt2")
    prompts = _prompts((8, 8), seed=3)
    refs = _refs(decode_model, params, prompts, temperature=0.0,
                 max_new=20)
    engine = InferenceEngine(model, params, num_slots=2, temperature=0.0)
    report = engine.run(_requests(prompts, max_new=20))
    assert report["metrics"]["preempted"] >= 1
    for i in range(2):
        r = report["results"][f"r{i}"]
        assert r["status"] == "done"
        assert r["tokens"] == refs[i]


def test_inflight_insertion_slot_isolation():
    """A request inserted at a decode boundary never perturbs resident
    requests' logits: every request's tokens equal its solo run."""
    decode_model, paged_model, params = _family("gpt2")
    prompts = _prompts((8, 5, 7), seed=4)
    sample_kw = dict(temperature=1.0, top_k=5)
    refs = _refs(decode_model, params, prompts, max_new=12, **sample_kw)
    clock = VirtualClock()
    engine = InferenceEngine(
        paged_model, params, num_slots=3, clock=clock, sleep=clock.sleep,
        **sample_kw,
    )
    # r2 arrives while r0/r1 are mid-decode (virtual clock ticks per read)
    reqs = _requests(prompts[:2], max_new=12)
    reqs.append(Request(rid="r2", prompt=[int(t) for t in prompts[2]],
                        max_new_tokens=12, seed=2, arrival=0.02))
    report = engine.run(reqs)
    assert report["metrics"]["admitted"] == 3
    for i in range(3):
        assert report["results"][f"r{i}"]["tokens"] == refs[i]


def test_continuous_beats_static_batching():
    """Mixed-length workload over 2 slots: continuous batching needs
    strictly fewer decode-program launches (the deterministic throughput
    proxy; a wall-clock margin needs a serving cell on the chip: PERF §7)."""
    _, paged_model, params = _family("gpt2")
    prompts = _prompts((8, 8, 8, 8), seed=5)
    reqs = [
        Request(rid=f"r{i}", prompt=[int(t) for t in p],
                max_new_tokens=n, seed=i)
        for i, (p, n) in enumerate(zip(prompts, (4, 16, 4, 16)))
    ]
    engine = InferenceEngine(
        paged_model, params, num_slots=2, temperature=0.0
    )
    cont = engine.run(reqs, mode="continuous")["metrics"]
    stat = engine.run(reqs, mode="static")["metrics"]
    assert cont["completed"] == stat["completed"] == 4
    assert cont["decode_steps"] < stat["decode_steps"]
    assert cont["slot_occupancy"] > stat["slot_occupancy"]


# ---------------------------------------------------------------------------
# scheduler unit tests: pure host bookkeeping, virtual clock, no jax
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(num_blocks=9, block_size=4, max_blocks_per_slot=8,
                num_slots=2)
    base.update(kw)
    return PagedCacheConfig(**base)


def test_admission_blocks_when_pool_exhausted():
    sched = Scheduler(_cfg())  # 8 allocatable blocks
    # each request: 12-token prompt -> blocks_for(13) = 4 blocks
    a = sched.submit(Request(rid="a", prompt=[0] * 12, max_new_tokens=4), 0.0)
    b = sched.submit(Request(rid="b", prompt=[0] * 12, max_new_tokens=4), 0.0)
    c = sched.submit(Request(rid="c", prompt=[0] * 12, max_new_tokens=4), 0.0)
    admitted = sched.admit(1.0)
    assert [s.request.rid for s in admitted] == ["a", "b"]
    assert sched.allocator.free_count() == 0
    assert sched.admit(2.0) == []  # c blocked: no blocks, no free slot
    # eviction recycles a's blocks; c then admits into the freed slot
    slot_a, slot_b = a.slot, b.slot
    sched.finish(a, "done", now=3.0)
    assert sched.allocator.free_count() == 4
    assert [s.request.rid for s in sched.admit(4.0)] == ["c"]
    assert c.slot == slot_a != slot_b


def test_blocks_recycled_exactly_on_eviction():
    def replay():
        sched = Scheduler(_cfg())
        st = sched.submit(
            Request(rid="a", prompt=[0] * 6, max_new_tokens=20), 0.0
        )
        sched.admit(0.0)
        held = list(st.blocks)
        assert sched.allocator.free_count() == 8 - len(held)
        # simulate decode growth past a block boundary
        st.generated.extend([1] * 4)  # cached_len 9 -> needs 3 blocks
        assert sched.grow(st)
        assert len(st.blocks) == 3
        sched.finish(st, "done", now=1.0)
        assert sched.allocator.free_count() == 8
        assert st.blocks == [] and st.slot == -1
        st2 = sched.submit(
            Request(rid="b", prompt=[0] * 6, max_new_tokens=4), 2.0
        )
        sched.admit(2.0)
        return held, list(st2.blocks)

    # deterministic replay: the identical op sequence allocates the
    # identical block ids both times (the chaos bit-identical lean)
    assert replay() == replay()


def test_head_of_line_fifo_no_overtake():
    sched = Scheduler(_cfg(num_slots=3))  # 8 allocatable blocks
    a = sched.submit(Request(rid="a", prompt=[0] * 12, max_new_tokens=2), 0.0)
    assert [s.request.rid for s in sched.admit(0.0)] == ["a"]  # 4 blocks
    big = sched.submit(
        Request(rid="big", prompt=[0] * 20, max_new_tokens=2), 1.0
    )  # needs blocks_for(21) = 6 > 4 free -> blocked at head of line
    small = sched.submit(
        Request(rid="small", prompt=[0] * 2, max_new_tokens=2), 1.0
    )  # needs 1 block and a slot is free -- but must NOT overtake big
    assert sched.admit(1.0) == []
    sched.finish(a, "done", now=2.0)  # frees 4 -> 8 free
    assert [s.request.rid for s in sched.admit(3.0)] == ["big", "small"]
    assert big.slot != small.slot


def test_static_mode_admits_only_drained_waves():
    sched = Scheduler(_cfg(), mode="static")
    for i in range(4):
        sched.submit(
            Request(rid=f"r{i}", prompt=[0] * 2, max_new_tokens=2), 0.0
        )
    wave1 = sched.admit(0.0)
    assert len(wave1) == 2
    # one slot drains; static mode still refuses to backfill
    sched.finish(wave1[0], "done", now=1.0)
    assert sched.admit(1.0) == []
    sched.finish(wave1[1], "done", now=2.0)
    assert len(sched.admit(2.0)) == 2  # the next full wave


def test_preempt_youngest_requeues_at_front():
    sched = Scheduler(_cfg())
    a = sched.submit(Request(rid="a", prompt=[0] * 4, max_new_tokens=4), 0.0)
    b = sched.submit(Request(rid="b", prompt=[0] * 4, max_new_tokens=4), 0.0)
    sched.admit(0.0)
    a.generated.append(1)
    b.generated.append(1)
    victim = sched.preempt_youngest()
    assert victim is b  # the most recently admitted resident
    assert b.status == "queued" and b.generated == [] and b.blocks == []
    assert sched.queue[0] is b  # front of the line: keeps FIFO seniority
    assert b.preemptions == 1
    assert sched.counters["preempted"] == 1


def test_submit_rejects_never_fit():
    sched = Scheduler(_cfg())
    bad = sched.submit(
        Request(rid="x", prompt=[0] * 30, max_new_tokens=10), 0.0
    )  # 40 > max_context 32
    assert bad.status == "rejected"
    empty = sched.submit(Request(rid="y", prompt=[], max_new_tokens=4), 0.0)
    assert empty.status == "rejected"
    assert sched.counters["rejected"] == 2
    assert not sched.queue


def test_allocator_shard_affinity():
    cfg = PagedCacheConfig(num_blocks=16, block_size=4,
                           max_blocks_per_slot=4, num_slots=4, num_shards=2)
    alloc = BlockAllocator(cfg)
    # slots map onto contiguous shard ranges; scratch only costs shard 0
    assert [alloc.shard_of_slot(s) for s in range(4)] == [0, 0, 1, 1]
    assert alloc.free_count(0) == 7 and alloc.free_count(1) == 8
    got = alloc.alloc(3, shard=1)
    assert got is not None and all(8 <= b < 16 for b in got)
    assert alloc.alloc(8, shard=0) is None  # all-or-nothing
    alloc.release(got)
    assert alloc.free_count(1) == 8
    with pytest.raises(ValueError, match="scratch"):
        alloc.release([0])


def test_paged_model_requires_decode_mode():
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    with pytest.raises(ValueError, match="decode"):
        GPT2(**GPT2_KW, **PAGED).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )


# ---------------------------------------------------------------------------
# speculative decoding: bit-identical output, fewer decode boundaries
# ---------------------------------------------------------------------------


def _spec_engine(paged_model, params, spec_tokens=4, **kw):
    """Self-speculation (draft = target): zero model risk, and the
    exact-match acceptance rule is exercised identically to a real small
    draft — only the accept RATE differs."""
    return InferenceEngine(
        paged_model, params, draft_model=paged_model, draft_params=params,
        spec_tokens=spec_tokens, **kw,
    )


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_spec_greedy_token_exact(family):
    """Speculative greedy == generate(): acceptance commits only drafts
    the target would have emitted, so the output is the non-speculative
    stream bit-for-bit — while taking strictly fewer decode boundaries."""
    decode_model, paged_model, params = _family(family)
    prompts = _prompts((8, 5, 11), seed=6)
    refs = _refs(decode_model, params, prompts, max_new=12, temperature=0.0)
    plain = InferenceEngine(
        paged_model, params, num_slots=2, temperature=0.0
    )
    plain_steps = plain.run(_requests(prompts, max_new=12))["metrics"][
        "decode_steps"
    ]
    engine = _spec_engine(paged_model, params, num_slots=2, temperature=0.0)
    report = engine.run(_requests(prompts, max_new=12))
    for i in range(len(prompts)):
        r = report["results"][f"r{i}"]
        assert r["status"] == "done"
        assert r["tokens"] == refs[i]
    # the boundary amortization actually happened (greedy self-spec
    # accepts every draft, so ~K tokens commit per boundary)
    assert report["metrics"]["decode_steps"] < plain_steps


def test_spec_seeded_sampling_token_exact():
    """Exact-match acceptance is temperature-independent: the verify step
    samples each window position with the SAME position-folded key the
    sequential path would use, so sampled speculative output reproduces
    generate(rng_fold="position") bit-for-bit too."""
    decode_model, paged_model, params = _family("gpt2")
    prompts = _prompts((8, 5, 11), seed=7)
    sample_kw = dict(temperature=0.9, top_k=5)
    refs = _refs(decode_model, params, prompts, max_new=10, **sample_kw)
    engine = _spec_engine(paged_model, params, num_slots=2, **sample_kw)
    report = engine.run(_requests(prompts, max_new=10))
    for i in range(len(prompts)):
        assert report["results"][f"r{i}"]["tokens"] == refs[i]


def test_spec_preemption_restart_bit_identical():
    """Block pressure with a speculative window in flight: the preempted
    request replays to the same tokens — speculative growth is clamped to
    the request ceiling and the rng folds absolute positions, so the
    accept/reject sequence replays exactly."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    decode_model, _, params = _family("gpt2")
    model = GPT2(**GPT2_KW, decode=True, paged_num_blocks=12,
                 paged_block_size=4, paged_max_blocks=8)
    prompts = _prompts((8, 8), seed=8)
    refs = _refs(decode_model, params, prompts, temperature=0.0,
                 max_new=20)
    engine = _spec_engine(model, params, num_slots=2, temperature=0.0)
    report = engine.run(_requests(prompts, max_new=20))
    assert report["metrics"]["preempted"] >= 1
    for i in range(2):
        r = report["results"][f"r{i}"]
        assert r["status"] == "done"
        assert r["tokens"] == refs[i]


def test_spec_metrics_reported():
    """The report carries the serve-line decode metrics: tokens/sec over
    decode-boundary wall time and the drafted-token accept rate (1.0 for
    greedy self-speculation except final-window ceiling truncation)."""
    _, paged_model, params = _family("gpt2")
    prompts = _prompts((8, 5), seed=9)
    engine = _spec_engine(paged_model, params, num_slots=2, temperature=0.0)
    m = engine.run(_requests(prompts, max_new=12))["metrics"]
    assert m["decode_tokens"] > 0
    assert m["decode_tokens_per_sec"] > 0
    assert m["spec_accept_rate"] is not None
    assert 0.8 <= m["spec_accept_rate"] <= 1.0
    plain = InferenceEngine(
        paged_model, params, num_slots=2, temperature=0.0
    )
    pm = plain.run(_requests(prompts, max_new=12))["metrics"]
    assert pm["spec_accept_rate"] is None  # speculation off -> no rate
    assert pm["decode_tokens"] > 0


def test_spec_requires_matching_geometry():
    """A draft with a different paged geometry cannot share the engine's
    table layout; the constructor refuses it up front."""
    from distributed_pytorch_example_tpu.models.gpt2 import GPT2

    _, paged_model, params = _family("gpt2")
    other = GPT2(**GPT2_KW, decode=True, paged_num_blocks=16,
                 paged_block_size=8, paged_max_blocks=4)
    with pytest.raises(ValueError, match="geometry|paged"):
        InferenceEngine(
            paged_model, params, draft_model=other, draft_params=params,
            spec_tokens=4,
        )
    with pytest.raises(ValueError, match="spec_tokens"):
        InferenceEngine(
            paged_model, params, draft_model=paged_model,
            draft_params=params, spec_tokens=1,
        )
