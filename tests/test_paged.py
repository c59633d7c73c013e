"""Paged KV-cache + continuous batching: allocator, kernel-vs-oracle,
paged-vs-contiguous token equality, page reuse, scheduler admit/evict,
prefix-sharing/CoW, the batched ragged admission prefill, and the
quota-aware resource manager (growth-on-demand paging, host-swap
preemption/restore, multi-tenant budgets + DRR, prefix retention)."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.data.synthetic import lm_tokens
from repro.kernels.flash_decode_paged import flash_decode_paged
from repro.kernels.flash_prefill_ragged import flash_prefill_ragged
from repro.launch.serve import generate, make_serve_fns
from repro.models import layers as L
from repro.models.api import build_model
from repro.serving import (ContinuousBatchingScheduler, PageAllocator,
                           PagedCacheConfig, PagedServingEngine,
                           PrefixCache, Request, TenantConfig,
                           TRASH_PAGE, init_paged_cache)
from repro.serving.paged_cache import (gather_request_kv,
                                       pages_from_contiguous, pool_shape,
                                       read_pages, write_pages,
                                       write_token_kv)

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------- allocator
class TestPageAllocator:
    def test_alloc_free_reuse(self):
        a = PageAllocator(8)                       # pages 1..7 allocatable
        p1 = a.alloc(3)
        assert p1 == [1, 2, 3]
        assert a.n_free == 4
        a.release(p1)
        assert a.n_free == 7
        # freed pages are reused first, lowest-first
        assert a.alloc(2) == [1, 2]

    def test_never_hands_out_trash_page(self):
        a = PageAllocator(4)
        assert TRASH_PAGE not in a.alloc(3)

    def test_all_or_nothing(self):
        a = PageAllocator(4)
        assert a.alloc(3) is not None
        assert a.alloc(1) is None                  # exhausted
        assert a.n_free == 0

    def test_double_free_rejected(self):
        a = PageAllocator(4)
        p = a.alloc(2)
        a.release(p)
        with pytest.raises(ValueError):
            a.release(p)

    def test_capacity_validation(self):
        pcfg = PagedCacheConfig(page_size=4, n_pages=8, max_slots=2,
                                max_blocks=2)
        with pytest.raises(ValueError):
            pcfg.validate_request(prompt_len=8, max_new_tokens=4)
        assert pcfg.validate_request(prompt_len=4, max_new_tokens=3) == 2


# ------------------------------------------------- position/mask helpers
class TestPagedMaskHelpers:
    def test_matches_contiguous_helpers(self):
        """Per-request paged positions/mask rows must equal the linear
        contiguous-cache helpers at the same position."""
        n_slots = 24
        seq_lens = jnp.asarray([0, 5, 23], jnp.int32)
        kv_pos = L.paged_kv_positions(seq_lens, n_slots)
        mask = L.paged_decode_attention_mask(kv_pos, seq_lens)
        for i, pos in enumerate([0, 5, 23]):
            ref_pos = L.kv_positions_for_cache(jnp.asarray(pos), n_slots, 0)
            ref_mask = L.decode_attention_mask(ref_pos, pos, 0)
            assert bool(jnp.all(kv_pos[i] == ref_pos))
            assert bool(jnp.all(mask[i] == ref_mask))

    def test_ragged_rows(self):
        seq_lens = jnp.asarray([2, 7], jnp.int32)
        mask = L.paged_decode_attention_mask(
            L.paged_kv_positions(seq_lens, 8), seq_lens)
        assert mask.astype(int).sum(axis=1).tolist() == [3, 8]


# ------------------------------------------------------ kernel vs oracle
def _paged_problem(key, slots, h, kvh, d, page_size, blocks, seq_lens):
    """Random pages + a scrambled block table + per-request lengths."""
    ks = jax.random.split(key, 4)
    n_pages = slots * blocks + 1
    q = jax.random.normal(ks[0], (slots, 1, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, kvh, page_size, d),
                           jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, kvh, page_size, d),
                           jnp.float32)
    perm = jax.random.permutation(ks[3], n_pages - 1) + 1
    bt = perm[:slots * blocks].reshape(slots, blocks).astype(jnp.int32)
    return q, kp, vp, bt, jnp.asarray(seq_lens, jnp.int32)


def _oracle(q, kp, vp, bt, sl):
    """Direct masked softmax over the shared mask helper."""
    slots, _, h, d = q.shape
    ps = kp.shape[2]
    n = bt.shape[1] * ps
    mask = L.paged_decode_attention_mask(L.paged_kv_positions(sl, n), sl)
    kf = gather_request_kv(kp, bt)
    vf = gather_request_kv(vp, bt)
    k_exp = L._expand_kv(kf, h)
    v_exp = L._expand_kv(vf, h)
    s = jnp.einsum("bqhd,bkhd->bhqk",
                   q.astype(jnp.float32) / math.sqrt(d),
                   k_exp.astype(jnp.float32))
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v_exp.astype(jnp.float32))


class TestPagedKernelVsOracle:
    @pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (7, 1)])
    @pytest.mark.parametrize("page_size", [4, 8, 16])
    def test_gqa_and_page_size_grid(self, h, kvh, page_size):
        slots, blocks, d = 3, 3, 8
        cap = blocks * page_size
        seq_lens = [0, cap // 2, cap - 1]          # empty-ish / mid / full
        q, kp, vp, bt, sl = _paged_problem(
            KEY, slots, h, kvh, d, page_size, blocks, seq_lens)
        out = flash_decode_paged(q, kp[None], vp[None], bt, sl, 0,
                                 interpret=True)
        ref = _oracle(q, kp, vp, bt, sl)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    @pytest.mark.parametrize("seq_lens", [[0, 1, 2, 3], [5, 17, 9, 30]])
    def test_ragged_lengths(self, seq_lens):
        slots, blocks, ps, h, kvh, d = 4, 4, 8, 4, 2, 8
        q, kp, vp, bt, sl = _paged_problem(
            jax.random.PRNGKey(7), slots, h, kvh, d, ps, blocks, seq_lens)
        out = flash_decode_paged(q, kp[None], vp[None], bt, sl, 0,
                                 interpret=True)
        ref = _oracle(q, kp, vp, bt, sl)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_scrambled_vs_identity_block_table(self):
        """Physical page placement must be invisible: the same logical
        K/V through a scrambled table equals an identity layout."""
        slots, blocks, ps, h, kvh, d = 2, 3, 4, 4, 2, 8
        seq_lens = [7, 11]
        q, kp, vp, bt, sl = _paged_problem(
            jax.random.PRNGKey(3), slots, h, kvh, d, ps, blocks, seq_lens)
        ident_bt = 1 + jnp.arange(slots * blocks,
                                  dtype=jnp.int32).reshape(slots, blocks)
        kp_i = kp.at[ident_bt.reshape(-1)].set(kp[bt.reshape(-1)])
        vp_i = vp.at[ident_bt.reshape(-1)].set(vp[bt.reshape(-1)])
        out_s = flash_decode_paged(q, kp[None], vp[None], bt, sl, 0,
                                   interpret=True)
        out_i = flash_decode_paged(q, kp_i[None], vp_i[None], ident_bt, sl,
                                   0, interpret=True)
        assert float(jnp.max(jnp.abs(out_s - out_i))) < 1e-6

    @pytest.mark.parametrize("layer", [0, 2])
    def test_layer_of_whole_model_pool(self, layer):
        """Given a 3-layer pool and a layer, the kernel attends that
        layer's pages, as the oracle reading ``pool[layer]`` does."""
        slots, blocks, ps, h, kvh, d = 3, 3, 8, 4, 2, 8
        q, kp, vp, bt, sl = _paged_problem(
            jax.random.PRNGKey(13), slots, h, kvh, d, ps, blocks,
            [4, 13, 23])
        ks = jax.random.split(jax.random.PRNGKey(14), 2)
        kp3 = jax.random.normal(ks[0], (3,) + kp.shape)
        vp3 = jax.random.normal(ks[1], (3,) + vp.shape)
        out = flash_decode_paged(q, kp3, vp3, bt, sl, layer, interpret=True)
        ref = _oracle(q, kp3[layer], vp3[layer], bt, sl)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def _slot_probe_pool(bt, n_pages, kvh, ps):
    """K = 0 (every live slot scores alike) and V = one-hot of the logical
    slot, scattered through the block table: the kernel's output row is
    then 1/|live| on exactly the slots it attended."""
    r, blocks = bt.shape
    n = blocks * ps
    logical = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32)[None, :, None],
                               (r, n, kvh, n))
    vp = jnp.zeros((n_pages, kvh, ps, n), jnp.float32)
    vp = vp.at[bt.reshape(-1)].set(
        logical.reshape(r, blocks, ps, kvh, n).transpose(0, 1, 3, 2, 4)
        .reshape(r * blocks, kvh, ps, n))
    return jnp.zeros_like(vp), vp


class TestDecodePredicate:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("ps,blocks", [(4, 5), (8, 3), (16, 2)])
    def test_in_kernel_predicate_equals_mask_helper(self, seed, ps, blocks):
        """The kernel attends exactly the slots
        paged_decode_attention_mask marks, across ragged lengths (empty,
        page-aligned, full) and scrambled block tables."""
        slots, h, kvh = 4, 4, 2
        n = blocks * ps
        key = jax.random.PRNGKey(seed)
        lens = jax.random.randint(key, (slots,), 0, n)
        sl = lens.at[0].set(0).at[1].set(ps - 1).at[2].set(n - 1)
        n_pages = slots * blocks + 1
        perm = jax.random.permutation(jax.random.fold_in(key, 1),
                                      n_pages - 1) + 1
        bt = perm[:slots * blocks].reshape(slots, blocks).astype(jnp.int32)
        kp, vp = _slot_probe_pool(bt, n_pages, kvh, ps)
        q = jax.random.normal(jax.random.fold_in(key, 2), (slots, 1, h, n))
        out = flash_decode_paged(q, kp[None], vp[None], bt, sl, 0,
                                 interpret=True)
        mask = L.paged_decode_attention_mask(L.paged_kv_positions(sl, n), sl)
        attended = np.asarray(out[:, 0] > 0)                  # (R, H, n)
        want = np.broadcast_to(np.asarray(mask)[:, None], attended.shape)
        assert np.array_equal(attended, want)

    def test_pages_past_length_are_never_read(self):
        """A page wholly past a request's length is skipped: NaN there
        leaves the output finite and equal to the clean pool's."""
        slots, blocks, ps, h, kvh, d = 3, 4, 8, 4, 2, 8
        seq_lens = [3, 12, 0]
        q, kp, vp, bt, sl = _paged_problem(
            jax.random.PRNGKey(11), slots, h, kvh, d, ps, blocks, seq_lens)
        dead = [int(bt[r, j]) for r in range(slots) for j in range(blocks)
                if j * ps > seq_lens[r]]
        assert dead
        kp_nan = kp.at[jnp.asarray(dead)].set(jnp.nan)
        vp_nan = vp.at[jnp.asarray(dead)].set(jnp.nan)
        out = flash_decode_paged(q, kp_nan[None], vp_nan[None], bt, sl, 0,
                                 interpret=True)
        ref = flash_decode_paged(q, kp[None], vp[None], bt, sl, 0,
                                 interpret=True)
        assert bool(jnp.all(jnp.isfinite(out)))
        assert float(jnp.max(jnp.abs(out - ref))) == 0.0


class TestPoolLayout:
    """The page-layout helpers every pool reader and writer goes
    through, against explicit per-token loops."""

    def test_pool_shape_is_head_major(self):
        cfg = get_config("qwen2_7b", smoke=True)
        pcfg = PagedCacheConfig(page_size=8, n_pages=5)
        assert pool_shape(cfg, pcfg) == (cfg.n_layers, 5, cfg.n_kv_heads,
                                         8, cfg.hd)
        cache, _ = init_paged_cache(cfg, pcfg)
        assert cache["blocks"]["k_pages"].shape == pool_shape(cfg, pcfg)

    def test_token_scatter_and_request_gather(self):
        n_pages, kvh, ps, hd = 6, 2, 4, 3
        rng = np.random.default_rng(0)
        bt = np.array([[3, 1], [5, 2]], np.int32)
        pos = np.array([[0, 5, 7], [2, 3, 4]])              # (R, S)
        kv = rng.normal(size=pos.shape + (kvh, hd)).astype(np.float32)
        rows = np.arange(2)[:, None]
        pages = write_token_kv(jnp.zeros((1, n_pages, kvh, ps, hd)), 0,
                               bt[rows, pos // ps], pos % ps, kv)[0]
        want = np.zeros((n_pages, kvh, ps, hd), np.float32)
        for r in range(2):
            for i, p in enumerate(pos[r]):
                want[bt[r, p // ps], :, p % ps] = kv[r, i]
        assert np.array_equal(np.asarray(pages), want)
        got = np.asarray(gather_request_kv(pages, jnp.asarray(bt)))
        assert got.shape == (2, 2 * ps, kvh, hd)
        for r in range(2):
            for i, p in enumerate(pos[r]):
                assert np.array_equal(got[r, p], kv[r, i])

    @pytest.mark.parametrize("layer", [0, 1, 2])
    def test_token_scatter_writes_one_layer(self, layer):
        """A token write leaves every other layer bit-identical, and on its
        own layer equals the per-layer write of one (KV, hd) window per
        token."""
        n_layers, n_pages, kvh, ps, hd = 3, 6, 2, 4, 3
        rng = np.random.default_rng(2)
        pool = rng.normal(size=(n_layers, n_pages, kvh, ps,
                                hd)).astype(np.float32)
        pidx = np.array([3, 1, 5, 3], np.int32)
        slot = np.array([0, 2, 3, 1], np.int32)
        kv = rng.normal(size=(4, kvh, hd)).astype(np.float32)
        got = np.asarray(write_token_kv(jnp.asarray(pool), layer,
                                        jnp.asarray(pidx),
                                        jnp.asarray(slot),
                                        jnp.asarray(kv)))
        per_layer = jnp.asarray(pool[layer]).at[pidx, :, slot].set(kv)
        for i in range(n_layers):
            want = np.asarray(per_layer) if i == layer else pool[i]
            assert np.array_equal(got[i], want)

    def test_unrolled_stack_equals_scan(self):
        """The unrolled layer loop (the cost probes' route) takes the same
        paged body as the scan: same logits, same pools (float32
        activations, so that fusion choices round nothing)."""
        cfg = get_config("qwen2_7b", smoke=True).replace(act_dtype="float32")
        model = build_model(cfg)
        params = model.init(KEY)
        pcfg = PagedCacheConfig(page_size=4, n_pages=9, max_slots=2,
                                max_blocks=4)
        cache, _ = init_paged_cache(cfg, pcfg, jnp.float32)
        shape = cache["blocks"]["k_pages"].shape
        ks = jax.random.split(jax.random.PRNGKey(21), 2)
        cache = dict(cache, blocks={
            "k_pages": jax.random.normal(ks[0], shape),
            "v_pages": jax.random.normal(ks[1], shape)},
            block_tables=jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]],
                                     jnp.int32),
            seq_lens=jnp.asarray([5, 10], jnp.int32))
        tok = jnp.asarray([[3], [7]], jnp.int32)
        unrolled = build_model(cfg.replace(scan_layers=False))
        lg_s, c_s = jax.jit(model.decode_step)(params, cache, tok)
        lg_u, c_u = jax.jit(unrolled.decode_step)(params, cache, tok)
        assert np.allclose(np.asarray(lg_s), np.asarray(lg_u), atol=1e-5)
        for name in ("k_pages", "v_pages"):
            assert np.allclose(np.asarray(c_s["blocks"][name]),
                               np.asarray(c_u["blocks"][name]), atol=1e-6)

    def test_page_images_round_trip(self):
        n_layers, kvh, ps, hd = 2, 2, 4, 3
        rng = np.random.default_rng(1)
        contiguous = rng.normal(size=(n_layers, 1, 3 * ps, kvh,
                                      hd)).astype(np.float32)
        image = pages_from_contiguous(jnp.asarray(contiguous), ps)
        assert image.shape == (n_layers, 3, kvh, ps, hd)
        for b in range(3):
            for s in range(ps):
                assert np.array_equal(np.asarray(image[:, b, :, s]),
                                      contiguous[:, 0, b * ps + s])
        pool = jnp.zeros((n_layers, 7, kvh, ps, hd))
        rows = jnp.asarray([4, 1, 6])
        pool = write_pages(pool, rows, image)
        assert np.array_equal(np.asarray(read_pages(pool, rows)),
                              np.asarray(image))


# ------------------------------------- engine: paged vs contiguous tokens
def _smoke_setup():
    cfg = get_config("qwen2_7b", smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params


def _prompts(cfg, n, prompt_len, seed=1):
    return np.asarray(lm_tokens(n * prompt_len, cfg.vocab_size, seed=seed)
                      ).reshape(n, prompt_len).astype(np.int32)


def _contiguous_tokens(model, params, prompts, gen):
    fns = make_serve_fns(model)
    out = {}
    for i in range(prompts.shape[0]):
        toks = generate(model, params, jnp.asarray(prompts[i:i + 1]), gen,
                        prompts.shape[1] + gen + 1, scan=True, fns=fns)
        out[i] = [int(t) for t in np.asarray(toks)[0]]
    return out


class TestPagedEngineTokens:
    @pytest.mark.parametrize("page_size", [8, 16, 32])
    def test_tokens_equal_contiguous_across_page_sizes(self, page_size):
        cfg, model, params = _smoke_setup()
        prompt_len, gen, n = 16, 9, 3
        prompts = _prompts(cfg, n, prompt_len)
        base = _contiguous_tokens(model, params, prompts, gen)
        blocks = -(-(prompt_len + gen + 1) // page_size)
        pcfg = PagedCacheConfig(page_size=page_size,
                                n_pages=2 * blocks * 2 + 1,
                                max_slots=2, max_blocks=blocks,
                                segment_len=4)
        eng = PagedServingEngine(model, pcfg)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gen)
                for i in range(n)]
        eng.run(reqs, params)
        for r in reqs:
            assert r.tokens == base[r.rid], (page_size, r.rid)

    def test_kernel_path_tokens_equal_oracle_path(self):
        cfg, model, params = _smoke_setup()
        model_k = build_model(cfg, use_kernels=True, interpret=True)
        prompt_len, gen, n = 16, 8, 3
        prompts = _prompts(cfg, n, prompt_len, seed=5)
        pcfg = PagedCacheConfig(page_size=8, n_pages=16, max_slots=2,
                                max_blocks=4, segment_len=4)
        res = {}
        for name, mdl in (("oracle", model), ("kernel", model_k)):
            reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gen)
                    for i in range(n)]
            PagedServingEngine(mdl, pcfg).run(reqs, params)
            res[name] = {r.rid: r.tokens for r in reqs}
        assert res["oracle"] == res["kernel"]

    def test_ragged_max_new_tokens(self):
        """Requests finishing at different steps: each still matches its
        own contiguous reference."""
        cfg, model, params = _smoke_setup()
        prompt_len = 16
        gens = [3, 11, 7, 5]
        prompts = _prompts(cfg, len(gens), prompt_len, seed=9)
        fns = make_serve_fns(model)
        base = {}
        for i, g in enumerate(gens):
            toks = generate(model, params, jnp.asarray(prompts[i:i + 1]),
                            g, prompt_len + g + 1, scan=True, fns=fns)
            base[i] = [int(t) for t in np.asarray(toks)[0]]
        pcfg = PagedCacheConfig(page_size=8, n_pages=16, max_slots=3,
                                max_blocks=4, segment_len=4)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=g)
                for i, g in enumerate(gens)]
        PagedServingEngine(model, pcfg).run(reqs, params)
        for r in reqs:
            assert len(r.tokens) == gens[r.rid]
            assert r.tokens == base[r.rid]

    def test_page_reuse_after_completion(self):
        """A pool sized for ~one request at a time forces later requests
        onto recycled pages; tokens must stay correct."""
        cfg, model, params = _smoke_setup()
        prompt_len, gen, n = 16, 6, 4
        prompts = _prompts(cfg, n, prompt_len, seed=3)
        base = _contiguous_tokens(model, params, prompts, gen)
        pcfg = PagedCacheConfig(page_size=8, n_pages=4, max_slots=2,
                                max_blocks=3, segment_len=2)
        # pages_for(16+6+1)=3 = entire allocatable pool: strictly serial
        # admission, every admission after the first reuses freed pages
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gen)
                for i in range(n)]
        eng = PagedServingEngine(model, pcfg)
        eng.run(reqs, params)
        for r in reqs:
            assert r.tokens == base[r.rid]


# -------------------------------------------------------------- scheduler
class TestScheduler:
    def test_admit_evict_across_segments(self):
        """More requests than slots: admissions must be spread over the
        run (continuous batching), not all up front, and every request
        completes with freed pages accounted for."""
        cfg, model, params = _smoke_setup()
        prompt_len, gen, n = 16, 6, 5
        prompts = _prompts(cfg, n, prompt_len, seed=11)
        pcfg = PagedCacheConfig(page_size=8, n_pages=8, max_slots=2,
                                max_blocks=3, segment_len=2)
        eng = PagedServingEngine(model, pcfg)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gen)
                for i in range(n)]
        stats = eng.run(reqs, params)
        assert stats["n_finished"] == n
        assert all(len(r.tokens) == gen for r in reqs)
        # 5 requests through 2 slots cannot be co-resident: admissions
        # must span multiple scheduler syncs
        admit_times = sorted(r.t_admitted for r in reqs)
        done_times = sorted(r.t_done for r in reqs)
        assert admit_times[-1] > done_times[0]

    def test_admission_blocks_on_pages_not_just_slots(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=4, max_slots=4,
                                max_blocks=3)
        sched = ContinuousBatchingScheduler(pcfg)
        for i in range(3):
            sched.submit(Request(rid=i, prompt=np.zeros(8, np.int32),
                                 max_new_tokens=8))
        admitted = sched.try_admit()
        # each request needs pages_for(8+8+1)=3 pages; pool has 3 free
        assert len(admitted) == 1
        assert sched.pending and sched.free_slots
        sched.complete(admitted[0].slot)
        assert len(sched.try_admit()) == 1

    def test_fifo_no_overtaking(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=4, max_slots=4,
                                max_blocks=3)
        sched = ContinuousBatchingScheduler(pcfg)
        big = Request(rid="big", prompt=np.zeros(16, np.int32),
                      max_new_tokens=7)
        small = Request(rid="small", prompt=np.zeros(4, np.int32),
                        max_new_tokens=2)
        filler = Request(rid="filler", prompt=np.zeros(8, np.int32),
                         max_new_tokens=6)
        sched.submit(filler)
        assert [r.rid for r in sched.try_admit()] == ["filler"]  # 2 pages
        sched.submit(big)      # needs 3 pages, only 1 free
        sched.submit(small)    # would fit, but must not overtake big
        assert sched.try_admit() == []

    def test_trash_page_never_allocated(self):
        cfg, _, _ = _smoke_setup()
        pcfg = PagedCacheConfig(page_size=8, n_pages=6, max_slots=2,
                                max_blocks=3)
        cache, _ = init_paged_cache(cfg, pcfg)
        assert bool(jnp.all(cache["block_tables"] == TRASH_PAGE))
        sched = ContinuousBatchingScheduler(pcfg)
        sched.submit(Request(rid=0, prompt=np.zeros(8, np.int32),
                             max_new_tokens=8))
        (req,) = sched.try_admit()
        assert TRASH_PAGE not in req.pages

    def test_paging_gated_families(self):
        from repro.serving.paged_cache import supports_paging
        assert supports_paging(get_config("qwen2_7b", smoke=True))
        assert not supports_paging(
            get_config("h2o_danube_3_4b", smoke=True))   # sliding window
        assert not supports_paging(
            get_config("zamba2_2p7b", smoke=True))       # hybrid SSM
        with pytest.raises(ValueError):
            PagedServingEngine(
                build_model(get_config("h2o_danube_3_4b", smoke=True)),
                PagedCacheConfig())


# ------------------------------------------------------ autotune problem
class TestPagedAutotune:
    def test_registered_and_tunable(self, tmp_path):
        from repro.kernels import autotune
        prob = autotune.flash_decode_paged_problem(2, 4, 2, 8, 16,
                                                   "float32")
        cands = autotune.enumerate_candidates("flash_decode_paged", prob)
        assert {"page_size": 16} in [c for c, _ in cands]  # default
        res = autotune.tune("flash_decode_paged", prob,
                            cache_path=str(tmp_path / "c.json"), iters=1)
        assert res.config["page_size"] >= 1
        again = autotune.tune("flash_decode_paged", prob,
                              cache_path=str(tmp_path / "c.json"),
                              iters=1)
        assert again.cached and again.config == res.config

    def test_tune_task_derives_paged_problem(self):
        from repro.tasks.tune import derive_problems
        from repro.tasks.handle import DNNHandle
        cfg = get_config("qwen2_7b", smoke=True)
        model = build_model(cfg)
        params = model.init(KEY)
        handle = DNNHandle(kind="lm", name="m", params=params,
                           model=model)
        probs = derive_problems(handle, max_problems=16)
        kernels = [p["kernel"] for p in probs]
        assert "flash_decode_paged" in kernels
        # windowed arch: ring-buffer cache is not paged -> no paged problem
        wcfg = get_config("h2o_danube_3_4b", smoke=True)
        wmodel = build_model(wcfg)
        whandle = DNNHandle(kind="lm", name="w", params=wmodel.init(KEY),
                            model=wmodel)
        wkernels = [p["kernel"]
                    for p in derive_problems(whandle, max_problems=16)]
        assert "flash_decode_paged" not in wkernels


# ----------------------------------------------- refcounts + prefix trie
class TestRefcountedAllocator:
    def test_share_release_lifecycle(self):
        a = PageAllocator(8)
        pages = a.alloc(3)
        a.share(pages[:2])                      # map into a second request
        assert a.refcount(pages[0]) == 2 and a.is_shared(pages[0])
        assert a.release(pages) == [pages[2]]   # only the unshared frees
        assert a.n_free == 5
        assert a.release(pages[:2]) == pages[:2]
        assert a.n_free == 7

    def test_share_free_page_rejected(self):
        a = PageAllocator(4)
        p = a.alloc(1)
        a.release(p)
        with pytest.raises(ValueError):
            a.share(p)

    def test_generation_bumps_on_realloc(self):
        a = PageAllocator(4)
        p = a.alloc(1)[0]
        g0 = a.generation(p)
        a.release([p])
        assert a.alloc(1) == [p]                # freed-first reuse
        assert a.generation(p) == g0 + 1


def _trie(n_pages=32, ps=8, chunk_pages=1):
    alloc = PageAllocator(n_pages)
    return alloc, PrefixCache(alloc, ps, chunk_pages=chunk_pages)


class TestPrefixCache:
    def test_full_chunk_match_and_always_leaves_suffix(self):
        alloc, pc = _trie()
        toks = np.arange(24, dtype=np.int32)
        pages = alloc.alloc(3)
        pc.insert(toks, 24, pages)
        pc.mark_ready()
        m = pc.lookup(toks)
        # 24 tokens = 3 aligned pages, but the last token must stay
        # unmatched (the admission still needs first-token logits), so
        # only the first 2 full pages are shareable
        assert list(m.pages) == pages[:2]
        assert m.n_tokens == 16 and m.tail_src is None

    def test_divergent_prompt_partial_match(self):
        alloc, pc = _trie()
        toks = np.arange(24, dtype=np.int32)
        pages = alloc.alloc(3)
        pc.insert(toks, 24, pages)
        pc.mark_ready()
        other = toks.copy()
        other[12] += 1                          # diverge inside page 2
        m = pc.lookup(other)
        assert list(m.pages) == pages[:1] and m.n_tokens == 8

    def test_tail_cow_match_requires_ready(self):
        alloc, pc = _trie()
        toks = np.arange(13, dtype=np.int32)
        pages = alloc.alloc(2)
        pc.insert(toks, 13, pages)
        m = pc.lookup(toks)                     # same boundary: not ready
        assert m.tail_src is None and m.n_tokens == 8
        pc.mark_ready()
        m = pc.lookup(toks)
        assert m.tail_src == pages[1]
        assert m.tail_tokens == 4               # 13 - 8 capped at len-1
        assert m.n_tokens == 12

    def test_entries_invalidate_after_free_and_realloc(self):
        alloc, pc = _trie(n_pages=4)
        toks = np.arange(16, dtype=np.int32)
        pages = alloc.alloc(2)
        pc.insert(toks, 16, pages)
        pc.mark_ready()
        alloc.release(pages)                    # owner completes
        assert pc.lookup(toks).n_tokens == 0    # refcount-0 page: stale
        other = np.arange(100, 116, dtype=np.int32)
        p2 = alloc.alloc(2)                     # same ids, new generation
        assert p2 == pages
        pc.insert(other, 16, p2)
        pc.mark_ready()
        assert pc.lookup(toks).n_tokens == 0    # old tokens never match
        assert pc.lookup(other).n_tokens == 8

    def test_chunk_pages_granularity(self):
        alloc, pc = _trie(ps=4, chunk_pages=2)  # 8-token match granule
        toks = np.arange(20, dtype=np.int32)
        pages = alloc.alloc(5)
        pc.insert(toks, 20, pages)
        pc.mark_ready()
        m = pc.lookup(toks)
        # two full 8-token chunks cover 4 pages; the 4-token tail page
        # is a CoW candidate at page granularity
        assert list(m.pages) == pages[:4]
        assert m.tail_src == pages[4] and m.n_tokens == 19


class TestPagedCacheConfigRoundTrip:
    def test_to_from_dict_roundtrip(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=17, max_slots=3,
                                max_blocks=5, segment_len=4,
                                enable_prefix_sharing=False,
                                prefix_chunk_pages=2, prefill_bucket=4)
        d = pcfg.to_dict()
        assert PagedCacheConfig.from_dict(d) == pcfg
        assert d["enable_prefix_sharing"] is False

    def test_from_dict_tolerates_old_and_future_configs(self):
        # a config persisted before the prefix-sharing knobs existed
        old = {"page_size": 8, "n_pages": 16, "max_slots": 2,
               "max_blocks": 4, "segment_len": 8}
        pcfg = PagedCacheConfig.from_dict(old)
        assert pcfg.enable_prefix_sharing          # default applies
        # and one persisted by a future version with an unknown knob
        fut = dict(old, some_future_knob=123)
        assert PagedCacheConfig.from_dict(fut).page_size == 8

    def test_checkpoint_extra_roundtrip(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        pcfg = PagedCacheConfig(page_size=8, prefix_chunk_pages=2)
        mgr.save(1, {"w": jnp.zeros((2,))},
                 extra={"paged_cache": pcfg.to_dict()})
        _, meta = mgr.restore()
        assert PagedCacheConfig.from_dict(
            meta["extra"]["paged_cache"]) == pcfg


# ------------------------------------------- ragged prefill kernel/oracle
def _ragged_problem(key, slots, s, h, kvh, d, ps, blocks, offs, lens):
    ks = jax.random.split(key, 3)
    n_pages = slots * blocks + 1
    q = jax.random.normal(ks[0], (slots, s, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, kvh, ps, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, kvh, ps, d), jnp.float32)
    perm = jax.random.permutation(ks[2], n_pages - 1) + 1
    bt = perm[:slots * blocks].reshape(slots, blocks).astype(jnp.int32)
    return (q, kp, vp, bt, jnp.asarray(offs, jnp.int32),
            jnp.asarray(lens, jnp.int32))


def _ragged_oracle(q, kp, vp, bt, offs, lens):
    """Direct masked softmax over the shared mask helper — independent of
    both the kernel and the mea-based layer oracle."""
    r, s, h, d = q.shape
    n = bt.shape[1] * kp.shape[2]
    kf = L._expand_kv(gather_request_kv(kp, bt), h)
    vf = L._expand_kv(gather_request_kv(vp, bt), h)
    mask = L.ragged_prefill_attention_mask(offs, lens, s, n)
    sgl = jnp.einsum("bqhd,bkhd->bhqk",
                     q.astype(jnp.float32) / math.sqrt(d),
                     kf.astype(jnp.float32))
    sgl = jnp.where(mask[:, None], sgl, -1e30)
    w = jax.nn.softmax(sgl, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, vf.astype(jnp.float32))
    valid = jnp.arange(s)[None] < lens[:, None]
    return jnp.where(valid[:, :, None, None], out, 0.0)


class TestRaggedPrefillKernel:
    @pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (7, 1)])
    @pytest.mark.parametrize("page_size", [4, 8, 16])
    def test_gqa_and_page_size_grid(self, h, kvh, page_size):
        slots, s, blocks, d = 3, 8, 4, 8
        offs = [0, page_size, 2 * page_size]     # suffix after a prefix
        lens = [8, 5, 0]                         # full / ragged / idle
        q, kp, vp, bt, off, ln = _ragged_problem(
            KEY, slots, s, h, kvh, d, page_size, blocks, offs, lens)
        out = flash_prefill_ragged(q, kp[None], vp[None], bt, off, ln, 0,
                                   interpret=True, block_q=4)
        ref = _ragged_oracle(q, kp, vp, bt, off, ln)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    @pytest.mark.parametrize("block_q", [2, 4, 8, 32])
    def test_block_q_grid_and_padding(self, block_q):
        """Ragged suffix lengths with q-tile padding: every tile size
        reduces to the same result (incl. bq > s, which clamps)."""
        slots, s, h, kvh, d, ps, blocks = 4, 7, 4, 2, 8, 8, 4
        offs = [0, 3, 8, 24]
        lens = [7, 4, 7, 1]
        q, kp, vp, bt, off, ln = _ragged_problem(
            jax.random.PRNGKey(3), slots, s, h, kvh, d, ps, blocks, offs,
            lens)
        out = flash_prefill_ragged(q, kp[None], vp[None], bt, off, ln, 0,
                                   interpret=True, block_q=block_q)
        ref = _ragged_oracle(q, kp, vp, bt, off, ln)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_mask_helper_is_single_source(self):
        """The kernel's in-kernel iota mask and the shared helper agree:
        flipping any single (query, slot) admissibility in the helper
        changes the oracle away from the kernel."""
        slots, s, h, kvh, d, ps, blocks = 2, 4, 2, 1, 8, 4, 3
        offs, lens = [2, 5], [4, 3]
        q, kp, vp, bt, off, ln = _ragged_problem(
            jax.random.PRNGKey(5), slots, s, h, kvh, d, ps, blocks, offs,
            lens)
        out = flash_prefill_ragged(q, kp[None], vp[None], bt, off, ln, 0,
                                   interpret=True)
        ref = _ragged_oracle(q, kp, vp, bt, off, ln)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
        # causal frontier sanity directly on the helper
        mask = L.ragged_prefill_attention_mask(off, ln, s, blocks * ps)
        assert mask[0, 0].astype(int).sum() == offs[0] + 1
        assert mask[1, 2].astype(int).sum() == offs[1] + 3
        assert not bool(mask[1, 3].any())        # past lens: dead row

    @pytest.mark.parametrize("layer", [0, 2])
    def test_layer_of_whole_model_pool(self, layer):
        """Given a 3-layer pool and a layer, the kernel attends that
        layer's pages, as the oracle reading ``pool[layer]`` does."""
        slots, s, h, kvh, d, ps, blocks = 3, 8, 4, 2, 8, 4, 4
        q, kp, vp, bt, off, ln = _ragged_problem(
            jax.random.PRNGKey(17), slots, s, h, kvh, d, ps, blocks,
            [0, 4, 8], [8, 5, 3])
        ks = jax.random.split(jax.random.PRNGKey(18), 2)
        kp3 = jax.random.normal(ks[0], (3,) + kp.shape)
        vp3 = jax.random.normal(ks[1], (3,) + vp.shape)
        out = flash_prefill_ragged(q, kp3, vp3, bt, off, ln, layer,
                                   interpret=True, block_q=4)
        ref = _ragged_oracle(q, kp3[layer], vp3[layer], bt, off, ln)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


# --------------------------------------- prefix-sharing engine behavior
def _serve_setup(arch="qwen2_7b"):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params


def _engine_tokens(model, params, pcfg, reqs_fn, mode):
    reqs = reqs_fn()
    stats = PagedServingEngine(model, pcfg, prefill_mode=mode).run(
        reqs, params)
    return {r.rid: list(r.tokens) for r in reqs}, stats


class TestPrefixSharingEngine:
    @pytest.mark.parametrize("arch", ["qwen2_7b", "starcoder2_3b"])
    @pytest.mark.parametrize("page_size", [8, 16])
    def test_burst_page_bound_and_token_equality(self, arch, page_size):
        """Acceptance: 8 requests sharing a page-aligned common prefix
        allocate no more than (unique tokens rounded up to pages) plus
        one CoW page per request, and generate tokens identical to the
        non-shared serial engine — across GQA ratios, page sizes, and
        ragged prompt lengths."""
        cfg, model, params = _serve_setup(arch)
        n, gen = 8, 4
        prefix_len = 2 * page_size              # page-aligned prefix
        suffixes = [3, 7, 1, page_size, 5, 2, 6, 4]   # ragged tails
        prefix = np.asarray(lm_tokens(prefix_len, cfg.vocab_size,
                                      seed=31)).astype(np.int32)
        prompts = [np.concatenate([
            prefix, np.asarray(lm_tokens(sfx, cfg.vocab_size,
                                         seed=40 + i)).astype(np.int32)])
            for i, sfx in enumerate(suffixes)]
        cap = prefix_len + max(suffixes) + gen + 1
        blocks = -(-cap // page_size)
        pcfg = PagedCacheConfig(page_size=page_size,
                                n_pages=n * blocks + 1, max_slots=n,
                                max_blocks=blocks, segment_len=4)
        mk = lambda: [Request(rid=i, prompt=prompts[i].copy(),  # noqa
                              max_new_tokens=gen) for i in range(n)]
        base, _ = _engine_tokens(model, params, pcfg, mk, "serial")
        got, stats = _engine_tokens(model, params, pcfg, mk, "batched")
        assert got == base
        # page bound: the prefix is allocated once; each request adds at
        # most its own unique tokens rounded up to pages, plus one CoW
        # page of allowance
        pages_unique = pcfg.pages_for(prefix_len) + sum(
            pcfg.pages_for(sfx + gen + 1) for sfx in suffixes)
        assert stats["pages_allocated_total"] <= pages_unique + n
        # and the sharing actually happened: 7 of 8 admissions hit
        assert stats["prefix_hits"] == n - 1
        assert stats["pages_shared_total"] >= \
            (n - 1) * pcfg.pages_for(prefix_len)

    def test_cow_tail_fork_across_boundaries(self):
        """A later admission whose prompt extends into a running owner's
        partially-filled tail page forks it copy-on-write; tokens still
        match the non-shared engine."""
        cfg, model, params = _serve_setup()
        plen = 13                               # 1 full page + 5-token tail
        prompt = np.asarray(lm_tokens(plen, cfg.vocab_size,
                                      seed=2)).astype(np.int32)
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=2,
                                max_blocks=4, segment_len=2)
        gens = [14, 2, 5]     # owner outlives B; C admitted mid-owner
        mk = lambda: [Request(rid=i, prompt=prompt.copy(),  # noqa
                              max_new_tokens=g)
                      for i, g in enumerate(gens)]
        base, _ = _engine_tokens(model, params, pcfg, mk, "serial")
        got, stats = _engine_tokens(model, params, pcfg, mk, "batched")
        assert got == base
        # C matched the owner's full page (8) AND its 4-token tail (the
        # 13th token always stays unmatched for first-token logits)
        assert stats["prefix_tokens_matched"] >= 8 + 12

    def test_sharing_disabled_by_config(self):
        cfg, model, params = _serve_setup()
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=4,
                                max_blocks=4, segment_len=4,
                                enable_prefix_sharing=False)
        prompt = np.asarray(lm_tokens(16, cfg.vocab_size,
                                      seed=7)).astype(np.int32)
        mk = lambda: [Request(rid=i, prompt=prompt.copy(),  # noqa
                              max_new_tokens=3) for i in range(3)]
        got, stats = _engine_tokens(model, params, pcfg, mk, "batched")
        assert stats["prefix_lookups"] == 0
        assert stats["pages_shared_total"] == 0
        base, _ = _engine_tokens(model, params, pcfg, mk, "serial")
        assert got == base

    def test_kernel_path_tokens_equal_oracle_shared(self):
        cfg, model, params = _serve_setup()
        model_k = build_model(cfg, use_kernels=True, interpret=True)
        prefix = np.asarray(lm_tokens(16, cfg.vocab_size,
                                      seed=3)).astype(np.int32)
        prompts = [np.concatenate([
            prefix, np.asarray(lm_tokens(sfx, cfg.vocab_size,
                                         seed=50 + sfx)).astype(np.int32)])
            for sfx in (3, 7, 9)]
        prompts.append(np.asarray(lm_tokens(11, cfg.vocab_size,
                                            seed=99)).astype(np.int32))
        pcfg = PagedCacheConfig(page_size=8, n_pages=40, max_slots=4,
                                max_blocks=5, segment_len=4)
        mk = lambda: [Request(rid=i, prompt=prompts[i].copy(),  # noqa
                              max_new_tokens=5)
                      for i in range(len(prompts))]
        oracle, _ = _engine_tokens(model, params, pcfg, mk, "batched")
        kernel, _ = _engine_tokens(model_k, params, pcfg, mk, "batched")
        serial, _ = _engine_tokens(model, params, pcfg, mk, "serial")
        assert oracle == serial
        assert kernel == serial


class TestBatchedPrefillBitIdentical:
    @pytest.mark.parametrize("plens", [(16, 13, 9), (8, 8, 8), (23,)])
    def test_pages_bit_identical_to_serial(self, plens):
        """Acceptance: batched ragged admission prefill writes exactly
        the same KV pages (and first tokens) as PR 3's serial batch-1
        prefill, bit for bit."""
        cfg, model, params = _serve_setup()
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=4,
                                max_blocks=4, segment_len=4)
        prompts = [np.asarray(lm_tokens(pl, cfg.vocab_size,
                                        seed=5 + i)).astype(np.int32)
                   for i, pl in enumerate(plens)]
        pools = {}
        for mode in ("serial", "batched"):
            eng = PagedServingEngine(model, pcfg, prefill_mode=mode)
            sched = ContinuousBatchingScheduler(pcfg, sharing=False)
            for i, p in enumerate(prompts):
                sched.submit(Request(rid=i, prompt=p, max_new_tokens=1))
            admitted = sched.try_admit()
            assert len(admitted) == len(prompts)
            cache, _ = init_paged_cache(cfg, pcfg, eng.cache_dtype)
            bt = np.full((pcfg.max_slots, pcfg.max_blocks), TRASH_PAGE,
                         np.int32)
            if mode == "batched":
                cache, toks, _, _ = eng._admit_batched(cache, bt,
                                                       admitted, params)
                first = [toks[r.slot] for r in admitted]
            else:
                first = []
                for req in admitted:
                    cache, t = eng._admit_serial(cache, bt, req, params)
                    first.append(t)
            pools[mode] = (np.asarray(cache["blocks"]["k_pages"]),
                           np.asarray(cache["blocks"]["v_pages"]),
                           first,
                           {r.rid: list(r.pages) for r in admitted})
        ks, vs, tok_s, pages = pools["serial"]
        kb, vb, tok_b, pages_b = pools["batched"]
        assert tok_s == tok_b
        assert pages == pages_b                  # same allocation order
        ps = pcfg.page_size
        for rid, pgs in pages.items():
            pl = len(prompts[rid])
            for bi in range(pcfg.pages_for(pl)):
                valid = min(ps, pl - bi * ps)
                pg = pgs[bi]
                assert np.array_equal(ks[:, pg, :, :valid],
                                      kb[:, pg, :, :valid]), (rid, bi)
                assert np.array_equal(vs[:, pg, :, :valid],
                                      vb[:, pg, :, :valid]), (rid, bi)


class TestRaggedPrefillAutotune:
    def test_registered_and_tunable(self, tmp_path):
        from repro.kernels import autotune
        prob = autotune.flash_prefill_ragged_problem(2, 16, 4, 2, 8, 32,
                                                     8, "float32")
        cands = autotune.enumerate_candidates("flash_prefill_ragged",
                                              prob)
        assert {"block_q": 32} in [c for c, _ in cands]   # default
        res = autotune.tune("flash_prefill_ragged", prob,
                            cache_path=str(tmp_path / "c.json"), iters=1)
        assert res.config["block_q"] >= 1
        again = autotune.tune("flash_prefill_ragged", prob,
                              cache_path=str(tmp_path / "c.json"),
                              iters=1)
        assert again.cached and again.config == res.config

    def test_tune_task_derives_ragged_prefill_problem(self):
        from repro.tasks.tune import derive_problems
        from repro.tasks.handle import DNNHandle
        cfg = get_config("qwen2_7b", smoke=True)
        model = build_model(cfg)
        handle = DNNHandle(kind="lm", name="m",
                           params=model.init(KEY), model=model)
        probs = derive_problems(handle, max_problems=16)
        fpr = [p for p in probs if p["kernel"] == "flash_prefill_ragged"]
        assert len(fpr) == 1
        # the page size (the prefix-match granule) rides in the problem:
        # TUNE tunes the suffix tile against the pool layout it selects
        assert fpr[0]["page_size"] >= 1
        wcfg = get_config("h2o_danube_3_4b", smoke=True)   # windowed
        wmodel = build_model(wcfg)
        whandle = DNNHandle(kind="lm", name="w",
                            params=wmodel.init(KEY), model=wmodel)
        wkernels = [p["kernel"]
                    for p in derive_problems(whandle, max_problems=16)]
        assert "flash_prefill_ragged" not in wkernels


class TestAdmissionOrdering:
    def test_same_boundary_sharer_with_longer_suffix(self):
        """Regression: a sharer whose own suffix outgrows its prefix
        owner's whole suffix (short cached system prompt + long user
        message, admitted at the same boundary) must not dispatch before
        the owner has written the shared pages."""
        cfg, model, params = _serve_setup()
        owner = np.asarray(lm_tokens(8, cfg.vocab_size,
                                     seed=61)).astype(np.int32)
        long_user = np.asarray(lm_tokens(32, cfg.vocab_size,
                                         seed=62)).astype(np.int32)
        sharer = np.concatenate([owner, long_user])
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=2,
                                max_blocks=6, segment_len=4)
        mk = lambda: [Request(rid=0, prompt=owner.copy(),  # noqa
                              max_new_tokens=4),
                      Request(rid=1, prompt=sharer.copy(),
                              max_new_tokens=4)]
        base, _ = _engine_tokens(model, params, pcfg, mk, "serial")
        got, stats = _engine_tokens(model, params, pcfg, mk, "batched")
        assert stats["prefix_tokens_matched"] == 8   # sharing did happen
        assert got == base

    def test_cow_dst_for_exactly_full_tail_page(self):
        """Regression: a matched tail that fills its page exactly
        (reachable with multi-page chunk granules) must fork into the
        page holding the last matched token, not one past it."""
        cfg, model, params = _serve_setup()
        owner_p = np.asarray(lm_tokens(20, cfg.vocab_size,
                                       seed=71)).astype(np.int32)
        sharer_p = np.concatenate([
            owner_p, np.asarray(lm_tokens(4, cfg.vocab_size,
                                          seed=72)).astype(np.int32)])
        pcfg = PagedCacheConfig(page_size=4, n_pages=40, max_slots=2,
                                max_blocks=10, segment_len=2,
                                prefix_chunk_pages=2)
        gens = [14, 2, 5]       # owner outlives filler; sharer joins late
        prompts = [owner_p, owner_p, sharer_p]
        mk = lambda: [Request(rid=i, prompt=prompts[i].copy(),  # noqa
                              max_new_tokens=g)
                      for i, g in enumerate(gens)]
        base, _ = _engine_tokens(model, params, pcfg, mk, "serial")
        got, stats = _engine_tokens(model, params, pcfg, mk, "batched")
        # the late sharer matched 2 full 8-token chunks + the full-page
        # 4-token tail of the running owner (20 of its 24 tokens)
        assert stats["prefix_tokens_matched"] >= 20
        assert got == base


# ----------------------------------- resource manager: growth on demand
class TestGrowthOnDemand:
    def test_admission_backs_one_segment_not_the_lifetime(self):
        """The old scheduler reserved prompt+max_new+1 at admission; the
        resource manager backs only prompt + one segment and grows the
        rest on demand."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=4,
                                max_blocks=5, segment_len=4)
        sched = ContinuousBatchingScheduler(pcfg)
        sched.submit(Request(rid=0, prompt=np.zeros(8, np.int32),
                             max_new_tokens=24))
        (req,) = sched.try_admit()
        # coverage: min(8 + 4 + 1, 8 + 24 + 1) = 13 tokens -> 2 pages,
        # against a 5-page lifetime
        assert len(req.pages) == 2
        assert sched.rm.lifetime_pages(req) == 5

    def test_packs_more_concurrent_requests_than_lifetime_reservation(self):
        """5 requests x 5 lifetime pages = 25 > the 11-page pool, but
        admission costs only 2 pages each — all five co-reside where
        whole-lifetime reservation could admit at most two."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=12, max_slots=5,
                                max_blocks=5, segment_len=4)
        sched = ContinuousBatchingScheduler(pcfg)
        for i in range(5):
            sched.submit(Request(rid=i, prompt=np.zeros(8, np.int32),
                                 max_new_tokens=24))
        assert len(sched.try_admit()) == 5

    def test_growth_happens_and_tokens_match_contiguous(self):
        """max_new far beyond one segment: pages arrive across several
        boundaries (pages_grown > 0) and tokens still equal the
        contiguous reference."""
        cfg, model, params = _smoke_setup()
        prompt_len, gen, n = 16, 12, 2
        prompts = _prompts(cfg, n, prompt_len, seed=17)
        base = _contiguous_tokens(model, params, prompts, gen)
        blocks = -(-(prompt_len + gen + 1) // 8)
        pcfg = PagedCacheConfig(page_size=8, n_pages=n * blocks + 1,
                                max_slots=n, max_blocks=blocks,
                                segment_len=2)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gen)
                for i in range(n)]
        stats = PagedServingEngine(model, pcfg).run(reqs, params)
        assert stats["pages_grown"] > 0
        assert stats["preemptions"] == 0       # pool fits both lifetimes
        for r in reqs:
            assert r.tokens == base[r.rid]


# ------------------------------ resource manager: preemption + restore
class TestPreemptionRestore:
    def test_oversubscribed_bit_identical_to_unconstrained(self):
        """Acceptance: total lifetime demand exceeds the pool, at least
        one preempt/restore cycle runs, every request completes, and
        per-request tokens are bit-identical to an unconstrained run."""
        cfg, model, params = _smoke_setup()
        prompt_len, gen, n = 16, 12, 4
        prompts = _prompts(cfg, n, prompt_len, seed=23)
        blocks = -(-(prompt_len + gen + 1) // 8)       # 4-page lifetime
        big = PagedCacheConfig(page_size=8, n_pages=n * blocks + 1,
                               max_slots=n, max_blocks=blocks,
                               segment_len=4)
        mk = lambda: [Request(rid=i, prompt=prompts[i].copy(),  # noqa
                              max_new_tokens=gen) for i in range(n)]
        reqs_u = mk()
        stats_u = PagedServingEngine(model, big).run(reqs_u, params)
        assert stats_u["preemptions"] == 0
        base = {r.rid: list(r.tokens) for r in reqs_u}
        # pool covers every admission (3 pages each) but not the
        # lifetimes (4 each): growth must preempt
        small = PagedCacheConfig(page_size=8, n_pages=n * 3 + 1,
                                 max_slots=n, max_blocks=blocks,
                                 segment_len=4)
        reqs = mk()
        stats = PagedServingEngine(model, small).run(reqs, params)
        assert stats["n_finished"] == n
        assert stats["preemptions"] >= 1
        assert stats["restores"] == stats["preemptions"]
        assert stats["pages_swapped_out"] > 0
        assert {r.rid: list(r.tokens) for r in reqs} == base

    def test_same_boundary_restore_and_fresh_sharer(self):
        """Regression: a fresh admission that prefix-shares a restore's
        pages at the SAME boundary must not prefill before the restore's
        host-image scatter has dispatched — full-chunk trie entries are
        matchable pre-ready by design, so the engine orders restores
        first.  Geometry: w1/w2 fill the 3 slots with r; their growth
        preempts r; both retire at one boundary, freeing slots+pages so
        r's restore and f's admission (same prompt as r) land together,
        with f sharing r's freshly re-allocated (scatter-pending) page."""
        cfg, model, params = _smoke_setup()
        P = np.asarray(lm_tokens(16, cfg.vocab_size,
                                 seed=77)).astype(np.int32)
        fillers = [np.asarray(lm_tokens(16, cfg.vocab_size,
                                        seed=78 + i)).astype(np.int32)
                   for i in range(2)]
        mk = lambda: [  # noqa: E731
            Request(rid="w1", prompt=fillers[0].copy(), max_new_tokens=8),
            Request(rid="w2", prompt=fillers[1].copy(), max_new_tokens=8),
            Request(rid="r", prompt=P.copy(), max_new_tokens=12),
            Request(rid="f", prompt=P.copy(), max_new_tokens=6)]
        big = PagedCacheConfig(page_size=8, n_pages=4 * 4 + 1,
                               max_slots=3, max_blocks=4, segment_len=4)
        ru = mk()
        PagedServingEngine(model, big).run(ru, params)
        base = {r.rid: list(r.tokens) for r in ru}
        small = PagedCacheConfig(page_size=8, n_pages=10, max_slots=3,
                                 max_blocks=4, segment_len=4)
        rs = mk()
        stats = PagedServingEngine(model, small).run(rs, params)
        assert stats["preemptions"] >= 1 and stats["restores"] >= 1
        assert stats["prefix_hits"] >= 1          # f did share r's pages
        assert {r.rid: list(r.tokens) for r in rs} == base

    def test_victim_policy_skips_protected_and_prefers_newest(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=4,
                                max_blocks=4, segment_len=4)
        sched = ContinuousBatchingScheduler(pcfg)
        for i in range(3):
            sched.submit(Request(rid=i, prompt=np.zeros(8, np.int32),
                                 max_new_tokens=4))
        admitted = sched.try_admit()
        assert len(admitted) == 3
        rm = sched.rm
        # all fresh admissions carry one segment of protection
        assert rm.pick_victim(sched.running.values(),
                              exclude=admitted[0]) is None
        sched.end_segment(r.slot for r in admitted)    # all generated
        victim = rm.pick_victim(sched.running.values(),
                                exclude=admitted[0])
        assert victim is admitted[2]                   # newest first
        admitted[2].protected = True                   # restored-like
        victim = rm.pick_victim(sched.running.values(),
                                exclude=admitted[0])
        assert victim is admitted[1]

    def test_restore_rematches_resident_prefix_pages(self):
        """A preempted request whose prompt prefix is still resident
        (its prefix owner kept running) restores by block-table aliasing
        for those pages and host swap-in only for the rest."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=2,
                                max_blocks=6, segment_len=4)
        sched = ContinuousBatchingScheduler(pcfg)
        prompt = np.arange(16, dtype=np.int32)
        owner = Request(rid="o", prompt=prompt, max_new_tokens=16)
        sharer = Request(rid="s", prompt=prompt.copy(), max_new_tokens=16)
        sched.submit(owner)
        sched.submit(sharer)
        admitted = sched.try_admit()
        assert len(admitted) == 2
        sched.finish_boundary(admitted)                # trie ready
        for r, ngen in ((owner, 4), (sharer, 2)):
            r.tokens = list(range(ngen))               # fake generation
        sched.end_segment([owner.slot, sharer.slot])
        owner_page0 = owner.pages[0]
        sched._preempt(sharer)
        assert sharer.swap is not None
        assert sharer.swap.n_tokens == 16 + 2 - 1      # sl = p + n_gen - 1
        (back,) = sched.try_admit()
        assert back is sharer
        # first prompt page re-mapped from the live owner, not swapped in
        assert back.restore_blocks[0] >= 1
        assert back.pages[0] == owner_page0
        assert sched.allocator.refcount(owner_page0) >= 2
        assert sched.rm.pages_swapped_in < sched.rm.pages_swapped_out

    def test_quota_preemption_stays_inside_the_tenant(self):
        """A tenant at its budget evicts its own newest request; other
        tenants' requests are never quota victims."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=4,
                                max_blocks=6, segment_len=4)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a", page_budget=5),
                           TenantConfig("b", page_budget=48)])
        reqs = [Request(rid=f"a{i}", prompt=np.zeros(8, np.int32),
                        max_new_tokens=24, tenant="a") for i in range(2)]
        other = Request(rid="b0", prompt=np.zeros(8, np.int32),
                        max_new_tokens=24, tenant="b")
        for r in [*reqs, other]:
            sched.submit(r)
        admitted = sched.try_admit()
        assert len(admitted) == 3
        for r in admitted:
            r.tokens = list(range(6))       # deep enough to need growth
        sched.end_segment(r.slot for r in admitted)
        preempted = sched.plan_growth()
        # tenant a is over budget for its growth: its newest request is
        # swapped; tenant b grows freely and is never touched
        assert preempted and all(r.tenant == "a" for r in preempted)
        assert other in sched.running.values()


# --------------------------- resource manager: tenants, DRR, retention
class TestTenantScheduling:
    def _mk(self, rid, tenant, max_new=4):
        return Request(rid=rid, prompt=np.zeros(8, np.int32),
                       max_new_tokens=max_new, tenant=tenant)

    def test_weighted_drr_admission_split(self):
        """Three slots, two tenants at weight 2:1 with equal-cost
        queues: the weight-2 tenant lands two of the three slots."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=3,
                                max_blocks=4, segment_len=4)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a", weight=2.0),
                           TenantConfig("b", weight=1.0)])
        for i in range(4):
            sched.submit(self._mk(f"a{i}", "a"))
            sched.submit(self._mk(f"b{i}", "b"))
        admitted = sched.try_admit()
        assert len(admitted) == 3
        by_tenant = {t: sum(r.tenant == t for r in admitted)
                     for t in ("a", "b")}
        assert by_tenant == {"a": 2, "b": 1}

    def test_budget_blocks_admission_until_pages_refund(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=4,
                                max_blocks=4, segment_len=8)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a", page_budget=3)])
        r1, r2 = self._mk("a0", "a", 8), self._mk("a1", "a", 8)
        sched.submit(r1)
        sched.submit(r2)
        admitted = sched.try_admit()   # 3 pages each: budget fits one
        assert [r.rid for r in admitted] == ["a0"]
        assert sched.rm.headroom("a") == 0
        sched.complete(r1.slot)        # refund through release_request
        assert sched.rm.headroom("a") == 3
        assert [r.rid for r in sched.try_admit()] == ["a1"]

    def test_lifetime_beyond_budget_rejected_at_submit(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=4,
                                max_blocks=6)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a", page_budget=2)])
        with pytest.raises(ValueError):
            sched.submit(self._mk("a0", "a", max_new=24))  # 5 pages

    def test_unknown_tenant_rejected_when_roster_is_explicit(self):
        """A typo'd tenant must not auto-register with a whole-pool
        budget and route around the configured quotas."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=4,
                                max_blocks=4)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a", page_budget=4)])
        with pytest.raises(ValueError, match="unknown tenant"):
            sched.submit(self._mk("x0", "a-typo"))
        # without a roster, any tenant name auto-registers (single-tenant
        # callers never mention tenants at all)
        open_sched = ContinuousBatchingScheduler(pcfg)
        open_sched.submit(self._mk("x0", "whatever"))
        assert len(open_sched.try_admit()) == 1

    def test_shared_prefix_pages_charge_only_marginal_cost(self):
        """A sharer whose prompt prefix is resident pays only for its
        CoW fork + suffix/decode pages — the shared pages never count
        against its tenant's budget."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=4,
                                max_blocks=6, segment_len=4)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a", page_budget=32),
                           TenantConfig("b", page_budget=5)])
        prompt = np.arange(24, dtype=np.int32)
        owner = Request(rid="o", prompt=prompt, max_new_tokens=8,
                        tenant="a")
        sched.submit(owner)
        sched.finish_boundary(sched.try_admit())
        owner_charged = owner.charged
        # sharer: 2 full prompt pages map free of charge — only the CoW
        # fork page and the fresh suffix/decode page are billed
        sharer = Request(rid="s", prompt=prompt.copy(), max_new_tokens=8,
                         tenant="b")
        sched.submit(sharer)
        (adm,) = sched.try_admit()
        assert adm is sharer
        assert sharer.shared_pages == 2
        assert sharer.charged == len(sharer.pages) - 2 == 2
        assert owner.charged == owner_charged  # owner pays for its own

    def test_tenant_stats_schema(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=2,
                                max_blocks=4)
        sched = ContinuousBatchingScheduler(
            pcfg, tenants=[TenantConfig("a")])
        sched.submit(self._mk("a0", "a"))
        sched.try_admit()
        stats = sched.stats()
        ta = stats["tenants"]["a"]
        for key in ("admitted", "preempted", "restored", "pages_swapped",
                    "pages_charged", "page_budget", "queued"):
            assert key in ta
        assert ta["admitted"] == 1 and ta["preempted"] == 0


class TestPrefixRetention:
    def test_pins_keep_prefix_alive_past_owner_completion(self):
        """With retain_pages set, completing the last request holding a
        prefix does NOT free its full-chunk pages — a later identical
        prompt still hits the trie."""
        pcfg = PagedCacheConfig(page_size=8, n_pages=32, max_slots=2,
                                max_blocks=6, segment_len=4,
                                retain_pages=2)
        sched = ContinuousBatchingScheduler(pcfg)
        prompt = np.arange(24, dtype=np.int32)
        req = Request(rid=0, prompt=prompt, max_new_tokens=4)
        sched.submit(req)
        sched.finish_boundary(sched.try_admit())   # pins the 2 full pages
        assert sched.prefix_cache.pinned_pages == 2
        pinned = req.pages[:2]
        sched.complete(req.slot)
        # pinned pages survive the owner's completion at refcount 1
        assert all(sched.allocator.refcount(p) == 1 for p in pinned)
        late = Request(rid=1, prompt=prompt.copy(), max_new_tokens=4)
        sched.submit(late)
        (adm,) = sched.try_admit()
        assert adm.shared_pages == 2
        assert adm.pages[:2] == pinned

    def test_pins_evict_under_allocator_pressure(self):
        """Retention never wins against a request's demand: an admission
        that needs the pinned pages gets them."""
        # 5 allocatable pages, 2 of them pinned after the owner leaves
        pcfg = PagedCacheConfig(page_size=8, n_pages=6, max_slots=2,
                                max_blocks=5, segment_len=8,
                                retain_pages=2)
        sched = ContinuousBatchingScheduler(pcfg)
        req = Request(rid=0, prompt=np.arange(24, dtype=np.int32),
                      max_new_tokens=8)
        sched.submit(req)
        sched.finish_boundary(sched.try_admit())
        sched.complete(req.slot)
        assert sched.prefix_cache.pinned_pages == 2
        assert sched.allocator.n_free == 3
        # unrelated request needing 5 pages: pins must yield
        big = Request(rid=1, prompt=100 + np.arange(24, dtype=np.int32),
                      max_new_tokens=8)
        sched.submit(big)
        (adm,) = sched.try_admit()
        assert adm is big and len(adm.pages) == 5
        assert sched.prefix_cache.pin_evictions >= 2
        assert sched.stats()["pin_evictions"] >= 2

    def test_pin_budget_is_lru_capped(self):
        pcfg = PagedCacheConfig(page_size=8, n_pages=64, max_slots=4,
                                max_blocks=6, segment_len=4,
                                retain_pages=3)
        sched = ContinuousBatchingScheduler(pcfg)
        for i in range(3):
            prompt = (100 * i + np.arange(24)).astype(np.int32)
            r = Request(rid=i, prompt=prompt, max_new_tokens=4)
            sched.submit(r)
            sched.finish_boundary(sched.try_admit())
            sched.complete(r.slot)
        pc = sched.prefix_cache
        assert pc.pinned_pages == 3          # capped, LRU evicted
        assert pc.pin_evictions == 6         # 9 candidate pins, 3 kept


# -------------------------------------------- segment-length autotuning
class TestSegmentAutotune:
    def test_registered_and_tunable(self, tmp_path):
        from repro.kernels import autotune
        prob = autotune.paged_segment_problem(2, 4, 2, 8, 24, 8,
                                              "float32")
        cands = autotune.enumerate_candidates("paged_segment", prob)
        assert {"segment_len": 8} in [c for c, _ in cands]   # default
        res = autotune.tune("paged_segment", prob,
                            cache_path=str(tmp_path / "c.json"), iters=1)
        assert res.config["segment_len"] >= 1
        again = autotune.tune("paged_segment", prob,
                              cache_path=str(tmp_path / "c.json"),
                              iters=1)
        assert again.cached and again.config == res.config

    def test_tune_task_derives_segment_problem(self):
        from repro.tasks.tune import derive_problems
        from repro.tasks.handle import DNNHandle
        cfg = get_config("qwen2_7b", smoke=True)
        model = build_model(cfg)
        handle = DNNHandle(kind="lm", name="m",
                           params=model.init(KEY), model=model)
        kernels = [p["kernel"]
                   for p in derive_problems(handle, max_problems=16)]
        assert "paged_segment" in kernels
        wcfg = get_config("h2o_danube_3_4b", smoke=True)   # windowed
        wmodel = build_model(wcfg)
        whandle = DNNHandle(kind="lm", name="w",
                            params=wmodel.init(KEY), model=wmodel)
        wkernels = [p["kernel"]
                    for p in derive_problems(whandle, max_problems=16)]
        assert "paged_segment" not in wkernels

    def test_preferred_segment_len_readback(self, tmp_path, monkeypatch):
        from repro.kernels import autotune
        from repro.serving.paged_cache import preferred_segment_len
        cache = str(tmp_path / "autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", cache)
        autotune.clear_memory_cache()
        cfg = get_config("qwen2_7b", smoke=True)
        # cold cache: the kernel default stands in
        assert preferred_segment_len(cfg, 4, 48) == 8
        # a persisted winner (keyed on the tuned page size) is read back
        prob = autotune.paged_segment_problem(
            4, cfg.n_heads, cfg.n_kv_heads, cfg.hd, 48, 16,
            str(cfg.adt))
        autotune._store(cache, autotune.cache_key("paged_segment", prob),
                        {"config": {"segment_len": 16}, "us": 1.0,
                         "n_trials": 5, "iters": 3,
                         "backend": jax.default_backend(), "t": 0.0})
        autotune.clear_memory_cache()
        assert preferred_segment_len(cfg, 4, 48) == 16
        autotune.clear_memory_cache()

    def test_growth_granule_follows_segment_len(self):
        pcfg = PagedCacheConfig(page_size=8, segment_len=12)
        assert pcfg.growth_granule == 2      # pages_for(12)
        assert PagedCacheConfig(page_size=8, segment_len=12,
                                growth_pages=3).growth_granule == 3
