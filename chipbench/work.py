"""The operations and bytes that the served work needs, from shapes and
live lengths alone.

What is counted is the work, not what an implementation does for it: valid
tokens only (no bucket padding, no idle or inactive rows), the lm_head once
per admitted request (its last prompt position) and once per decoded token,
and K/V that several rows read in the same step (a shared prefix) once.  So
a change that stops computing padding or unused logits reads as a gain and
never as a share above 100%.

FLOPs are those of the matrix products (2 per multiply-add): the
projections and MLP, attention's scores and weighted sum, and the lm_head.
Norms, RoPE and softmax are left out, as MFU conventionally leaves them.
Bytes are what the attention kernels must move through HBM: each query and
output row once, and each live K/V token once per step and layer.  Sizes
are in bfloat16 (2 bytes).

What differs between architectures comes from the configuration's plug-in
(``cell.arch_plugin``): the matmul parameters a token meets in one layer
(``Arch.layer_matmul_params``), the K/V bytes one token keeps in one layer
(``Arch.kv_token_bytes``) and how many tokens a query attends at a given
context, itself included (``Arch.attended``: the context itself under full
attention, at most the window under a sliding one).
"""

from __future__ import annotations

import dataclasses

BYTES = 2


@dataclasses.dataclass
class Work:
    flops: float = 0.0           # whole program (matmul FLOPs)
    attn_flops: float = 0.0      # the attention kernel's share of it
    attn_bytes: float = 0.0      # what the attention kernel must move

    def __iadd__(self, o: "Work") -> "Work":
        self.flops += o.flops
        self.attn_flops += o.attn_flops
        self.attn_bytes += o.attn_bytes
        return self


def admission(a, rows: list[dict]) -> Work:
    """One boundary's admissions.  Each row: ``offset`` (shared prefix
    tokens already in the pool), ``length`` (suffix tokens computed) and
    ``group`` (rows of one group share their prefix pages; None: alone).
    Each query attends causally over the prefix and suffix tokens that
    ``Arch.attended`` lets it reach."""
    w = Work()
    p, L, hq = a.layer_matmul_params(), a.layers, a.n_heads * a.hd
    kv_tokens = 0
    prefixes = {}
    for r in rows:
        o, n = int(r["offset"]), int(r["length"])
        if n <= 0:
            continue
        pairs = sum(a.attended(o + j) for j in range(1, n + 1))
        # the prefix tokens its first query attends: no later one
        # attends an earlier token
        seen = o - max(0, o + 1 - a.attended(o + 1))
        attn = 4 * hq * pairs * L
        w.flops += 2 * p * n * L + attn + 2 * a.d * a.vocab
        w.attn_flops += attn
        w.attn_bytes += 2 * n * hq * BYTES * L          # q in, out
        kv_tokens += n
        if r.get("group") is None:
            kv_tokens += seen
        else:
            prefixes[r["group"]] = max(prefixes.get(r["group"], 0), seen)
    w.attn_bytes += (kv_tokens + sum(prefixes.values())) \
        * a.kv_token_bytes() * L
    return w


def decode_step(a, rows: list[dict]) -> Work:
    """One decode step of the active rows.  Each row: ``context`` (tokens
    attended, the current one included), ``shared`` (how many of them are a
    prefix shared within ``group``) and ``group``."""
    w = Work()
    p, L, hq = a.layer_matmul_params(), a.layers, a.n_heads * a.hd
    kv_tokens = 0
    prefixes = {}
    for r in rows:
        c = int(r["context"])
        m = a.attended(c)
        attn = 4 * hq * m * L
        w.flops += 2 * p * L + attn + 2 * a.d * a.vocab
        w.attn_flops += attn
        w.attn_bytes += 2 * hq * BYTES * L
        s = int(r.get("shared", 0)) if r.get("group") is not None else 0
        s = max(0, s - (c - m))         # the shared tokens it attends
        kv_tokens += m - s
        if s:
            prefixes[r["group"]] = max(prefixes.get(r["group"], 0), s)
    w.attn_bytes += (kv_tokens + sum(prefixes.values())) \
        * a.kv_token_bytes() * L
    return w


def segment(a, rows: list[dict]) -> Work:
    """One decode segment.  Each row: ``context0`` (tokens in the pool
    before the segment's first step, the fed token excluded), ``emitted``
    (tokens it produced in the segment; 0 for an inactive row, which then
    costs nothing), ``shared`` and ``group``.  Step k of a row attends
    ``context0 + k + 1`` tokens."""
    w = Work()
    steps = max([int(r["emitted"]) for r in rows] + [0])
    for k in range(steps):
        w += decode_step(a, [
            {"context": int(r["context0"]) + k + 1,
             "shared": r.get("shared", 0), "group": r.get("group")}
            for r in rows if int(r["emitted"]) > k])
    return w
