"""Qwen2 (``model_type`` ``qwen2``; HF ``Qwen2ForCausalLM``,
arXiv:2407.10671), as published: RMSNorm, q/k/v with bias and o without,
rotate-half RoPE, grouped-query causal attention over the whole context,
a SwiGLU MLP and an untied lm_head.

A configuration whose file asks for what these equations leave out (a tied
head, another activation, sliding windows) is refused.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference import F32, HI, mm
from chipbench.work import BYTES

# what this plug-in's equations assume, as the file states it
ASSUMED = {"hidden_act": "silu", "tie_word_embeddings": False,
           "use_sliding_window": False}


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    n_heads: int
    n_kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    eps: float
    rope_theta: float

    # ------------------------------------------------------------ weights
    def _layer(self, key):
        d, h, kv, hd, ff = self.d, self.n_heads, self.n_kv, self.hd, self.ff
        k = iter(jax.random.split(key, 16))
        return {"ln1_w": W.norm_scale(next(k), (d,)),
                "ln2_w": W.norm_scale(next(k), (d,)),
                "q_w": W.normal(next(k), (d, h * hd), d ** -0.5),
                "k_w": W.normal(next(k), (d, kv * hd), d ** -0.5),
                "v_w": W.normal(next(k), (d, kv * hd), d ** -0.5),
                "o_w": W.normal(next(k), (h * hd, d), (h * hd) ** -0.5),
                "q_b": W.normal(next(k), (h * hd,), W.BIAS_STD),
                "k_b": W.normal(next(k), (kv * hd,), W.BIAS_STD),
                "v_b": W.normal(next(k), (kv * hd,), W.BIAS_STD),
                "gate_w": W.normal(next(k), (d, ff), d ** -0.5),
                "up_w": W.normal(next(k), (d, ff), d ** -0.5),
                "down_w": W.normal(next(k), (ff, d), ff ** -0.5)}

    def draw(self, key):
        k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
        k_norm, _ = jax.random.split(k_norm)
        return {"embed": W.by_rows(k_emb, self.vocab, self.d, W.EMBED_STD),
                "layers": jax.lax.map(self._layer,
                                      jax.random.split(k_layers, self.layers)),
                "norm_w": W.norm_scale(k_norm, (self.d,)),
                "head": W.by_rows(k_head, self.d, self.vocab, self.d ** -0.5)}

    def to_program(self, w):
        L, hd = w["layers"], self.hd
        return {"embed": w["embed"],
                "blocks": {"attn_norm": {"scale": L["ln1_w"]},
                           "attn": {"wq": W.heads_permuted(L["q_w"], hd),
                                    "wk": W.heads_permuted(L["k_w"], hd),
                                    "wv": L["v_w"], "wo": L["o_w"],
                                    "bq": W.heads_permuted(L["q_b"], hd),
                                    "bk": W.heads_permuted(L["k_b"], hd),
                                    "bv": L["v_b"]},
                           "mlp_norm": {"scale": L["ln2_w"]},
                           "mlp": {"w_gate": L["gate_w"], "w_up": L["up_w"],
                                   "w_down": L["down_w"]}},
                "final_norm": {"scale": w["norm_w"]},
                "lm_head": w["head"]}

    # ---------------------------------------------------------- reference
    def _norm(self, x, w):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return y * w.astype(F32)

    def _rope(self, x, pos):
        """Rotate-half RoPE: dims (i, i + hd/2) rotate by
        pos * theta^(-2i/hd).  x: (S, heads, hd)."""
        half = self.hd // 2
        inv = 1.0 / (self.rope_theta
                     ** (jnp.arange(half, dtype=F32) * 2 / self.hd))
        ang = pos[:, None, None].astype(F32) * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(self, lw, x, pos):
        s, h, kv, hd = x.shape[0], self.n_heads, self.n_kv, self.hd
        y = self._norm(x, lw["ln1_w"])
        q = (mm(y, lw["q_w"]) + lw["q_b"].astype(F32)).reshape(s, h, hd)
        k = (mm(y, lw["k_w"]) + lw["k_b"].astype(F32)).reshape(s, kv, hd)
        v = (mm(y, lw["v_w"]) + lw["v_b"].astype(F32)).reshape(s, kv, hd)
        q, k = self._rope(q, pos), self._rope(k, pos)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI).reshape(s, h * hd)
        x = x + mm(o, lw["o_w"])
        y = self._norm(x, lw["ln2_w"])
        f = jax.nn.silu(mm(y, lw["gate_w"])) * mm(y, lw["up_w"])
        return x + mm(f, lw["down_w"])

    def logits(self, w, h):
        return mm(self._norm(h, w["norm_w"]), w["head"])

    # -------------------------------------------------------------- work
    def layer_matmul_params(self) -> int:
        attn = self.d * self.hd * (2 * self.n_heads + 2 * self.n_kv)
        return attn + self.d * self.ff * 3

    def kv_token_bytes(self) -> int:
        return 2 * self.n_kv * self.hd * BYTES     # K and V of one token

    def attended(self, context: int) -> int:
        return context

    # ----------------------------------------------------------- program
    def check_program(self, cfg) -> dict:
        pairs = {"d_model": (cfg.d_model, self.d),
                 "n_heads": (cfg.n_heads, self.n_heads),
                 "n_kv_heads": (cfg.n_kv_heads, self.n_kv),
                 "hd": (cfg.hd, self.hd), "d_ff": (cfg.d_ff, self.ff),
                 "vocab_size": (cfg.vocab_size, self.vocab),
                 "tie_embeddings": (cfg.tie_embeddings, False),
                 "qkv_bias": (cfg.qkv_bias, True),
                 "norm": (cfg.norm, "rmsnorm"), "mlp": (cfg.mlp, "glu"),
                 "mlp_bias": (bool(cfg.mlp_bias), False)}
        return {k: v for k, v in pairs.items() if v[0] != v[1]}


def arch(config: dict) -> Arch:
    for key, want in ASSUMED.items():
        if config.get(key, want) != want:
            raise ValueError(f"{config['name']}: {key} = {config[key]!r}; "
                             f"the qwen2 plug-in covers {want!r} only")
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Arch(d=d, n_heads=h, n_kv=config["num_key_value_heads"],
                hd=config.get("head_dim", d // h),
                ff=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"],
                eps=float(config["rms_norm_eps"]),
                rope_theta=float(config["rope_theta"]))
