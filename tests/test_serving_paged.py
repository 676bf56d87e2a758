"""Paged-KV serving: the two admission bugfixes, the paged engine's
parity contract, and the admission front end.

Regression pins (both fail on the pre-fix engine):

* off-by-one output length — ``_admit`` appends the prefill-produced
  token but only ``_advance_slot`` checked termination, so
  ``max_new_tokens=1`` (or EOS on the prefill token) decoded an extra
  step and emitted an extra token;
* unvalidated prompt length — ``submit`` accepted ``len(prompt) >=
  max_len``, landing ``pos`` at the cache bound and silently truncating
  the request.

Paged contract (``serving.paged_kv`` + ``PagedServeEngine``):

* every page gather/scatter is a ``core.datatype`` descriptor pack —
  the unit tests drive append/gather/defrag/spill-reload directly on a
  synthetic cache tree and check byte round-trips;
* the paged engine is token-for-token identical to the contiguous
  engine under seeded random admission (FIFO preserved through the
  parked set), including with a tight pool + cold-prefix spill, and
  under the elastic loop's kill/repair path;
* ``AdmissionFrontEnd`` streams completions in completion order via
  ``engine.wait_any`` and bounces invalid offers instead of dying.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.progress import ProgressEngine
from repro.models import api
from repro.serving.engine import PagedServeEngine, ServeEngine
from repro.serving.paged_kv import PagedKVCache, PagedKVError, PoolExhausted

CFG = get_config("qwen1.5-0.5b", smoke=True)


@pytest.fixture(scope="module")
def params():
    return api.init_params(CFG, jax.random.key(0))


def _submit_seeded(eng, seed=3, n=9, lo=2, hi=12, mnt_hi=8):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(lo, hi))
        prompt = rng.integers(1, CFG.vocab, size=plen).astype(np.int32)
        reqs.append(eng.submit(prompt, max_new_tokens=int(rng.integers(1, mnt_hi))))
    return reqs


# ------------------------------------------------ bugfix 1: output length


def test_max_new_tokens_one_emits_exactly_one(params):
    eng = ServeEngine(CFG, params, max_batch=2, max_len=32)
    reqs = [
        eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=1),
        eng.submit(np.arange(3, 10, dtype=np.int32), max_new_tokens=3),
    ]
    eng.run_until_done(max_steps=50)
    assert all(r.done for r in reqs)
    # the contract length, not contract+1: the prefill-produced token IS
    # output token #1 and must be counted at admission
    assert [len(r.out_tokens) for r in reqs] == [1, 3]


def test_eos_on_prefill_token_emits_exactly_one(params):
    prompt = np.arange(2, 9, dtype=np.int32)
    # discover what the model emits for this prompt's prefill step
    probe = ServeEngine(CFG, params, max_batch=1, max_len=32)
    first = probe.submit(prompt, max_new_tokens=1)
    probe.run_until_done(max_steps=10)
    eos = first.out_tokens[0]

    eng = ServeEngine(CFG, params, max_batch=1, max_len=32)
    req = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    eng.run_until_done(max_steps=50)
    assert req.done
    assert req.out_tokens == [eos]  # EOS at admission, nothing decoded after


def test_done_at_admission_frees_the_slot_for_the_queue(params):
    # three done-at-admission requests + one real one through ONE slot:
    # the admission check must not burn a slot-step per finished request
    eng = ServeEngine(CFG, params, max_batch=1, max_len=32)
    quick = [eng.submit(np.arange(2, 7, dtype=np.int32), max_new_tokens=1) for _ in range(3)]
    slow = eng.submit(np.arange(4, 9, dtype=np.int32), max_new_tokens=4)
    eng.run_until_done(max_steps=60)
    assert [len(r.out_tokens) for r in quick] == [1, 1, 1]
    assert len(slow.out_tokens) == 4


# ------------------------------------------------ bugfix 2: prompt bounds


def test_submit_validates_prompt_length(params):
    eng = ServeEngine(CFG, params, max_batch=1, max_len=16)
    # boundary: max_len-1 admits and decodes
    ok = eng.submit(np.arange(1, 16, dtype=np.int32), max_new_tokens=2)
    assert len(ok.prompt) == 15
    # max_len (and beyond) raises instead of silently truncating
    with pytest.raises(ValueError, match="does not fit max_len"):
        eng.submit(np.arange(16, dtype=np.int32))
    with pytest.raises(ValueError, match="does not fit max_len"):
        eng.submit(np.arange(100, dtype=np.int32))
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.empty((0,), np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(3, dtype=np.int32), max_new_tokens=0)
    eng.run_until_done(max_steps=50)
    assert ok.done and len(ok.out_tokens) >= 1


def test_paged_submit_validates_too(params):
    eng = PagedServeEngine(CFG, params, max_batch=1, max_len=16, page_size=4)
    with pytest.raises(ValueError, match="does not fit max_len"):
        eng.submit(np.arange(16, dtype=np.int32))


# ------------------------------------------------ wait_any streaming order


def test_wait_any_streams_ragged_lengths_in_completion_order(params):
    pe = ProgressEngine()
    eng = ServeEngine(CFG, params, max_batch=2, max_len=32, progress_engine=pe)
    long = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
    short = eng.submit(np.arange(2, 7, dtype=np.int32), max_new_tokens=1)
    mid = eng.submit(np.arange(3, 8, dtype=np.int32), max_new_tokens=2)
    order = []
    pending = [long, short, mid]
    for _ in range(100):
        if eng._idle():
            break
        eng.step()
        while pending:
            done = eng.wait_any(pending, timeout=0.0)
            if done is None:
                break
            pending.remove(done)
            order.append(done)
    assert not pending
    # ragged outputs stream back as they finish, not in submission order:
    # `short` (1 token, admitted in the first wave) beats `long` (6), and
    # `mid` enters the slot `short` freed and still beats `long`
    assert order.index(short) < order.index(long)
    assert order.index(mid) < order.index(long)
    pe.stop_all()


def test_queue_longer_than_max_batch_exact_lengths(params):
    eng = ServeEngine(CFG, params, max_batch=2, max_len=32)
    rng = np.random.default_rng(11)
    want = [int(rng.integers(1, 6)) for _ in range(7)]
    reqs = [
        eng.submit(rng.integers(1, CFG.vocab, size=4).astype(np.int32), max_new_tokens=m)
        for m in want
    ]
    eng.run_until_done(max_steps=200)
    # 7 requests through 2 slots: every one completes with EXACTLY its
    # contract length (eos_id=-1 never fires)
    assert [len(r.out_tokens) for r in reqs] == want


# ------------------------------------------------ paged vs contiguous


@pytest.mark.parametrize("seed", [3, 7])
def test_paged_token_parity_under_seeded_admission(params, seed):
    contig = ServeEngine(CFG, params, max_batch=2, max_len=32)
    creqs = _submit_seeded(contig, seed=seed)
    contig.run_until_done(max_steps=300)

    paged = PagedServeEngine(
        CFG, params, max_batch=2, max_len=32, page_size=4, pool_pages=24
    )
    preqs = _submit_seeded(paged, seed=seed)
    paged.run_until_done(max_steps=300)

    assert [r.out_tokens for r in preqs] == [r.out_tokens for r in creqs]
    st = paged.stats()
    assert st["kv"]["pages_in_use"] == 0  # every page returned at release
    assert st["kv"]["appends"] > 0 and st["kv"]["gathers"] > 0
    # prefill-ahead parking admitted deeper than the slot count
    assert paged.max_concurrent > paged.max_batch


def test_paged_parity_with_tight_pool_and_spill(params):
    contig = ServeEngine(CFG, params, max_batch=2, max_len=32)
    creqs = _submit_seeded(contig, seed=3)
    contig.run_until_done(max_steps=300)

    pe = ProgressEngine()
    paged = PagedServeEngine(
        CFG,
        params,
        max_batch=2,
        max_len=32,
        page_size=4,
        pool_pages=9,
        spill_parked=True,
        progress_engine=pe,
    )
    preqs = _submit_seeded(paged, seed=3)
    paged.run_until_done(max_steps=300)
    assert [r.out_tokens for r in preqs] == [r.out_tokens for r in creqs]
    kv = paged.stats()["kv"]
    # the tight pool forced real spill/reload traffic through the window
    assert kv["spilled_pages"] > 0
    assert kv["reloaded_pages"] == kv["spilled_pages"]
    assert kv["cold_pages"] == 0 and kv["pages_in_use"] == 0
    pe.stop_all()


def test_paged_elastic_loop_token_parity_with_bugfixes(params):
    """Kill a worker mid-decode on the PAGED engine, with max_new_tokens=1
    requests in the mix: the transactional step repair re-appends spans
    idempotently and the output matches the fault-free contiguous oracle."""
    from repro.ft.faultinject import FaultEvent, FaultInjector, FaultPlan, VirtualClock

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab, (4 + i,)).astype(np.int32) for i in range(3)]
    mnts = [5, 1, 3]

    oracle = ServeEngine(CFG, params, max_batch=3, max_len=48)
    oreqs = [oracle.submit(p, max_new_tokens=m) for p, m in zip(prompts, mnts)]
    oracle.run_until_done(max_steps=200)
    want = [r.out_tokens for r in oreqs]
    assert len(want[1]) == 1  # the off-by-one fix holds inside the oracle

    pe = ProgressEngine()
    eng = PagedServeEngine(
        CFG, params, max_batch=3, max_len=48, page_size=8, progress_engine=pe
    )
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, mnts)]
    plan = FaultPlan([FaultEvent(0.0, "kill_rank", 1)])
    with FaultInjector(plan, clock=VirtualClock()) as inject:
        summary = eng.run_until_done_elastic(
            n_threads=3, fault_injector=inject, max_steps=200, sync_timeout=2.0
        )
    assert summary["dead_ranks"] == [1], summary
    assert [r.out_tokens for r in reqs] == want
    assert eng.stats()["kv"]["pages_in_use"] == 0
    pe.stop_all()


def test_paged_admits_deeper_than_contiguous_at_equal_memory(params):
    """The bench's equal-memory claim at test scale: same token-slot
    budget, the paged engine keeps more requests in flight than the
    contiguous engine has slots."""
    contig_slots, max_len, page_size = 4, 32, 4
    # paged: half the dense slots + the other half of the budget as pool
    paged = PagedServeEngine(
        CFG,
        params,
        max_batch=2,
        max_len=max_len,
        page_size=page_size,
        pool_pages=(contig_slots - 2) * (max_len // page_size),
    )
    rng = np.random.default_rng(5)
    for i in range(10):
        paged.submit(
            rng.integers(1, CFG.vocab, size=int(rng.integers(4, 8))).astype(np.int32),
            max_new_tokens=3 + i % 3,
        )
    paged.run_until_done(max_steps=400)
    assert paged.max_concurrent > contig_slots


# ------------------------------------------------ PagedKVCache unit tests


def _tree(max_len=16, batch=3, seed=0):
    """Synthetic two-leaf cache tree (mixed dtypes/shapes) + filled copy."""
    rng = np.random.default_rng(seed)
    template = {
        "k": jnp.zeros((2, batch, max_len, 4), jnp.float32),
        "v": jnp.zeros((1, batch, max_len, 2, 2), jnp.float32),
    }
    filled = {
        "k": jnp.asarray(rng.standard_normal((2, batch, max_len, 4)), jnp.float32),
        "v": jnp.asarray(rng.standard_normal((1, batch, max_len, 2, 2)), jnp.float32),
    }
    return template, filled


def _assert_gather_matches(kv, rid, filled, slot, upto):
    got = kv.gather(rid)
    for key in ("k", "v"):
        want = np.asarray(filled[key][:, slot : slot + 1, :upto])
        np.testing.assert_array_equal(np.asarray(got[key][:, :, :upto]), want)
        # positions past the stored length are zero (init_cache semantics)
        assert not np.asarray(got[key][:, :, upto:]).any()


def test_paged_kv_append_gather_roundtrip():
    template, filled = _tree()
    kv = PagedKVCache(template, max_len=16, page_size=4, num_pages=8)
    kv.alloc(7)
    kv.append(7, filled, slot=1, pos0=0, ntok=6)  # prefill: straddles a page
    kv.append(7, filled, slot=1, pos0=6, ntok=1)  # decode-step page view
    kv.append(7, filled, slot=1, pos0=7, ntok=1)
    assert kv.length(7) == 8 and kv.pages_in_use == 2
    _assert_gather_matches(kv, 7, filled, slot=1, upto=8)
    kv.release(7)
    assert kv.free_pages == 8


def test_paged_kv_append_is_idempotent_for_stored_spans():
    template, filled = _tree()
    kv = PagedKVCache(template, max_len=16, page_size=4, num_pages=8)
    kv.alloc(1)
    kv.append(1, filled, slot=0, pos0=0, ntok=5)
    kv.append(1, filled, slot=0, pos0=4, ntok=1)  # elastic repair replay
    assert kv.length(1) == 5
    _assert_gather_matches(kv, 1, filled, slot=0, upto=5)
    with pytest.raises(PagedKVError, match="past stored length"):
        kv.append(1, filled, slot=0, pos0=7, ntok=1)
    with pytest.raises(PagedKVError, match="straddles"):
        kv.append(1, filled, slot=0, pos0=4, ntok=3)


def test_paged_kv_rejects_non_positional_layouts():
    with pytest.raises(PagedKVError, match="position-indexed"):
        PagedKVCache({"k": jnp.zeros((2, 1, 8, 4))}, max_len=16, page_size=4)
    with pytest.raises(PagedKVError, match="cannot hold"):
        PagedKVCache(_tree()[0], max_len=16, page_size=4, num_pages=2)


def test_paged_kv_pool_exhaustion_and_release():
    template, filled = _tree()
    kv = PagedKVCache(template, max_len=16, page_size=4, num_pages=4)
    kv.alloc(1)
    kv.append(1, filled, slot=0, pos0=0, ntok=16)  # takes the whole pool
    kv.alloc(2)
    with pytest.raises(PoolExhausted):
        kv.append(2, filled, slot=1, pos0=0, ntok=1)
    kv.release(1)
    kv.append(2, filled, slot=1, pos0=0, ntok=3)
    _assert_gather_matches(kv, 2, filled, slot=1, upto=3)


def test_paged_kv_defrag_compacts_and_preserves_bytes():
    template, filled = _tree()
    kv = PagedKVCache(template, max_len=16, page_size=4, num_pages=8)
    for rid, slot in ((1, 0), (2, 1), (3, 2)):
        kv.alloc(rid)
        kv.append(rid, filled, slot=slot, pos0=0, ntok=8)
    kv.release(2)  # punch a 2-page hole in the middle
    out = kv.defrag()
    assert out == {"live_pages": 4, "moves": 2}
    # survivors compacted to the pool head, free list a dense tail
    assert sorted(kv.page_table(1) + kv.page_table(3)) == [0, 1, 2, 3]
    _assert_gather_matches(kv, 1, filled, slot=0, upto=8)
    _assert_gather_matches(kv, 3, filled, slot=2, upto=8)
    assert kv.free_pages == 4


def test_paged_kv_spill_reload_through_window():
    template, filled = _tree()
    pe = ProgressEngine()
    kv = PagedKVCache(template, max_len=16, page_size=4, num_pages=5, engine=pe)
    kv.alloc(1)
    kv.append(1, filled, slot=0, pos0=0, ntok=10)  # 2 full pages + tail
    assert kv.spillable(1) == 2
    assert kv.spill_prefix(1) == 2
    kv.reclaim(wait=True)
    assert kv.free_pages == 4  # spilled rows returned to the pool
    assert kv.page_table(1)[:2] == [None, None]
    # gather reloads the cold prefix and the bytes survive the round trip
    _assert_gather_matches(kv, 1, filled, slot=0, upto=10)
    st = kv.stats()
    assert st["spilled_pages"] == 2 and st["reloaded_pages"] == 2
    assert st["cold_pages"] == 0
    pe.stop_all()


# ------------------------------------------------ admission front end


def test_admission_front_end_streams_and_rejects(params):
    from repro.serving.admission import AdmissionFrontEnd, make_offer

    pe = ProgressEngine()
    eng = ServeEngine(CFG, params, max_batch=2, max_len=32, progress_engine=pe)
    fe = AdmissionFrontEnd(eng)

    def offers():
        rng = np.random.default_rng(7)
        for _ in range(6):
            plen = int(rng.integers(2, 12))
            yield make_offer(
                rng.integers(1, CFG.vocab, size=plen).astype(np.int32),
                max_new_tokens=int(rng.integers(1, 6)),
            )
        yield make_offer(np.arange(40, dtype=np.int32))  # over max_len

    done = []
    out = fe.serve(offers(), on_complete=done.append)
    assert len(out) == 6 and out == done
    # the invalid offer bounced at submit() instead of killing the loop
    assert len(fe.rejected) == 1
    assert "does not fit max_len" in fe.rejected[0]["error"]
    assert all(c.t_arrival <= c.t_submit <= c.t_first_token <= c.t_done for c in out)
    assert all(len(c.req.out_tokens) >= 1 for c in out)
    pe.stop_all()


def test_admission_front_end_paged_parity(params):
    from repro.serving.admission import AdmissionFrontEnd, make_offer

    def offers():
        rng = np.random.default_rng(13)
        for _ in range(7):
            yield make_offer(
                rng.integers(1, CFG.vocab, size=int(rng.integers(2, 10))).astype(np.int32),
                max_new_tokens=int(rng.integers(1, 5)),
            )

    outs = []
    for cls, kw in (
        (ServeEngine, {}),
        (PagedServeEngine, {"page_size": 4, "pool_pages": 24}),
    ):
        pe = ProgressEngine()
        eng = cls(CFG, params, max_batch=2, max_len=32, progress_engine=pe, **kw)
        cs = AdmissionFrontEnd(eng).serve(offers())
        outs.append([c.req.out_tokens for c in sorted(cs, key=lambda c: c.rid)])
        pe.stop_all()
    assert outs[0] == outs[1]


# ------------------------------------------------ bench-module drift pin


def test_run_py_imports_every_bench_module():
    """PR-5 fixed bench-list drift once; keep it pinned: every bench
    module in benchmarks/ must appear in run.py's module list."""
    import ast
    import pathlib

    bench_dir = pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
    mods = {
        p.stem
        for p in bench_dir.glob("*.py")
        if p.stem not in ("run", "__init__")
    }
    tree = ast.parse((bench_dir / "run.py").read_text())
    imported = {
        alias.name
        for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom) and node.module == "benchmarks"
        for alias in node.names
    }
    missing = mods - imported
    assert not missing, f"benchmarks/run.py does not import: {sorted(missing)}"
