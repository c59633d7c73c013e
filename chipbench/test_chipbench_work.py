"""The needed-work counts on small cases worked by hand, and at the chat
cell's widths on fixed rows."""

import pytest

from chipbench import cell as C
from chipbench import work

# d=4, 2 heads of 2 over 1 KV head, SwiGLU of 8, vocabulary 10, one layer:
# projections 4*2*(2*2 + 2*1) = 48 and MLP 3*4*8 = 96 -> 144 parameters.
A = C.arch_plugin("qwen2").Arch(d=4, n_heads=2, n_kv=1, hd=2, ff=8,
                                vocab=10, layers=1, eps=1e-6,
                                rope_theta=1e4)
LM_HEAD = 2 * 4 * 10          # one position's logits
KV_TOKEN = 2 * 1 * 2 * 2      # K and V of one token, bf16


def test_layer_params():
    assert A.layer_matmul_params() == 144


def test_admission_counts_valid_tokens_and_one_lm_head():
    w = work.admission(A, [{"offset": 0, "length": 3, "group": None}])
    # 6 causal (query, key) pairs; scores and weighted sum 4*H*hd a pair
    assert w.attn_flops == 4 * 4 * 6
    assert w.flops == 2 * 144 * 3 + 4 * 4 * 6 + LM_HEAD
    # q in and out once (3 rows x 4 x 2 B each), K/V of 3 tokens
    assert w.attn_bytes == 2 * 3 * 4 * 2 + 3 * KV_TOKEN


def test_admission_lm_head_once_whatever_the_length():
    for n in (1, 7, 100):
        w = work.admission(A, [{"offset": 0, "length": n, "group": None}])
        assert w.flops - 2 * 144 * n - w.attn_flops == LM_HEAD


def test_idle_rows_cost_nothing():
    one = work.admission(A, [{"offset": 5, "length": 3, "group": None}])
    padded = work.admission(A, [{"offset": 5, "length": 3, "group": None},
                                {"offset": 0, "length": 0, "group": None}])
    assert (one.flops, one.attn_bytes) == (padded.flops, padded.attn_bytes)


def test_admission_shared_prefix_read_once():
    rows = [{"offset": 16, "length": 4, "group": 7},
            {"offset": 16, "length": 4, "group": 7}]
    shared = work.admission(A, rows)
    alone = work.admission(A, [dict(r, group=None) for r in rows])
    assert shared.attn_bytes == 2 * 2 * 4 * 4 * 2 + (4 + 4 + 16) * KV_TOKEN
    assert alone.attn_bytes == 2 * 2 * 4 * 4 * 2 + 2 * 20 * KV_TOKEN
    assert shared.flops == alone.flops          # each still attends 16


def test_segment_inactive_rows_and_step_contexts():
    rows = [{"context0": 5, "emitted": 2, "shared": 0, "group": None},
            {"context0": 9, "emitted": 0, "shared": 0, "group": None}]
    w = work.segment(A, rows)
    # two steps of the active row, attending 6 then 7 tokens
    assert w.attn_flops == 4 * 4 * (6 + 7)
    assert w.flops == 2 * (2 * 144 + LM_HEAD) + 4 * 4 * 13
    assert w.attn_bytes == 2 * (2 * 4 * 2) + (6 + 7) * KV_TOKEN


def test_decode_shared_prefix_read_once_per_step():
    rows = [{"context": 20, "shared": 16, "group": 1},
            {"context": 18, "shared": 16, "group": 1}]
    w = work.decode_step(A, rows)
    assert w.attn_bytes == 2 * (2 * 4 * 2) + (4 + 2 + 16) * KV_TOKEN


# qwen2-7b's own widths (18 layers) on fixed rows: session rows, a lone
# prefix, and an inactive row.  Pinned: what the chat cell's rooflines and
# MFU divide by must not move unless the count is meant to.
ADMISSION = [{"offset": 0, "length": 300, "group": None},
             {"offset": 512, "length": 96, "group": 3},
             {"offset": 512, "length": 40, "group": 3},
             {"offset": 256, "length": 17, "group": None}]
SEGMENT = [{"context0": 311, "emitted": 8, "shared": 0, "group": None},
           {"context0": 700, "emitted": 5, "shared": 512, "group": 3},
           {"context0": 640, "emitted": 8, "shared": 512, "group": 3},
           {"context0": 90, "emitted": 0, "shared": 0, "group": None}]
PINNED = {"admission": (3837069250560.0, 32194842624.0, 161906688.0),
          "segment": (201961525248.0, 2888847360.0, 323739648.0)}


@pytest.mark.parametrize("kind", PINNED)
def test_chat_cell_counts_are_pinned(kind):
    a = C.arch(C.find_cell("qwen2-7b.chat").config)
    w = (work.admission(a, ADMISSION) if kind == "admission"
         else work.segment(a, SEGMENT))
    assert (w.flops, w.attn_flops, w.attn_bytes) == PINNED[kind]
