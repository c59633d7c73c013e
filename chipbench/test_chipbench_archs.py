"""Architectures are plug-ins found by name: ``archs/<model_type>.py`` from
the configuration file's ``model_type``.  A configuration of a new
``model_type`` comes with new files only (its plug-in, its configuration,
a mix) and entries in ``BENCHMARK.json``; a toy one, written to a scratch
checkout, goes through the cell lookup, the weight draw, the reference and
the work counts.  Smoke widths are the configuration file's own, and a mix
file hides a smoke stand-in of the same name."""

import json
import os

import numpy as np
import pytest

from chipbench import cell as C
from chipbench import reference, smoke, weights, work

TOY = '''
import dataclasses

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import mm
from chipbench.work import BYTES


@dataclasses.dataclass(frozen=True)
class Arch:
    """One head as wide as the model that averages the last ``window``
    tokens, a tanh projection, an untied head."""
    d: int
    vocab: int
    layers: int
    window: int
    n_heads: int = 1

    @property
    def hd(self):
        return self.d

    def draw(self, key):
        ke, kl, kh = jax.random.split(key, 3)
        return {"embed": weights.by_rows(ke, self.vocab, self.d, 1.0),
                "layers": {"w": weights.normal(
                    kl, (self.layers, self.d, self.d), self.d ** -0.5)},
                "head": weights.normal(kh, (self.d, self.vocab),
                                       self.d ** -0.5)}

    def to_program(self, w):
        return w

    def layer(self, lw, x, pos):
        back = pos[:, None] - pos[None, :]
        near = ((back >= 0) & (back < self.window)).astype(jnp.float32)
        mean = mm(near / near.sum(1, keepdims=True), x)
        return x + jnp.tanh(mm(mean, lw["w"]))

    def logits(self, w, h):
        return mm(h, w["head"])

    def layer_matmul_params(self):
        return self.d * self.d

    def kv_token_bytes(self):
        return self.d * BYTES

    def attended(self, context):
        return min(context, self.window)

    def check_program(self, cfg):
        return {}


def arch(config):
    return Arch(d=config["hidden_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"],
                window=config["sliding_window"])
'''
TOY_CONFIG = {"name": "toy-1", "model_type": "toy", "hidden_size": 8,
              "vocab_size": 32, "num_hidden_layers": 2, "sliding_window": 3}
MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 1.0},
       "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
       "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}


def write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def toy_checkout(root):
    bench = {"configs": [{"name": "toy-1", "source": "https://example.org",
                          "file": "chipbench/configs/toy-1.json",
                          "reduced": [], "why": "a toy"}],
             "workloads": [{"name": "toy-1.short", "config": "toy-1",
                            "traffic": "short", "chips": 1, "why": "a toy"}],
             "end_to_end": [{"name": "setup_s", "unit": "s",
                             "better": "lower", "bound": 0.25,
                             "source": "host_clock"}],
             "per_layer": []}
    write(root / "BENCHMARK.json", json.dumps(bench))
    write(root / "chipbench" / "configs" / "toy-1.json",
          json.dumps(TOY_CONFIG))
    write(root / "chipbench" / "traffic" / "short.json", json.dumps(MIX))
    write(root / "chipbench" / "archs" / "toy.py", TOY)
    return str(root)


def files(top):
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(top)
                  for f in fs if "__pycache__" not in d)


def test_plugin_is_found_by_model_type():
    config = C.find_cell("qwen2-7b.chat").config
    mod = C.arch_plugin(config["model_type"])
    assert mod.__file__ == os.path.join(C.HERE, "archs", "qwen2.py")
    a, again = C.arch(config), C.arch(dict(config))
    assert isinstance(a, mod.Arch) and a == again and hash(a) == hash(again)
    assert (a.d, a.layers, a.vocab) == (3584, 18, 152064)


@pytest.mark.parametrize("model_type", ["nosuch", "../cell"])
def test_unknown_model_type_is_an_error_naming_the_file(model_type):
    with pytest.raises(ValueError) as e:
        C.arch({"name": "x", "model_type": model_type})
    if model_type == "nosuch":
        assert os.path.join(C.HERE, "archs", "nosuch.py") in str(e.value)
    else:
        assert "not a plain name" in str(e.value)


@pytest.mark.parametrize("key,value", [("tie_word_embeddings", True),
                                       ("hidden_act", "gelu"),
                                       ("use_sliding_window", True)])
def test_plugin_refuses_what_its_equations_leave_out(key, value):
    config = dict(C.find_cell("qwen2-7b.chat").config, **{key: value})
    with pytest.raises(ValueError, match=key):
        C.arch(config)


def test_a_new_model_type_needs_only_new_files(tmp_path):
    before = files(C.HERE)
    root = toy_checkout(tmp_path)
    cell = C.find_cell("toy-1.short", root)
    assert cell.config == TOY_CONFIG and cell.traffic == MIX
    a = C.arch(cell.config, root)
    assert C.arch_plugin("toy", root).__file__ == os.path.join(
        root, "chipbench", "archs", "toy.py")

    w = weights.draw(a, weights.seed_key(2**33 + 1))
    assert w["embed"].shape == (32, 8) and w["layers"]["w"].shape == (2, 8, 8)
    # greedy tokens read a gap of 0; another token at the same row does not
    prompt = np.arange(5, dtype=np.int32)
    served = []
    for _ in range(6):
        _, lg = reference.served_gaps(a, w, prompt, served + [0])
        served.append(int(np.argmax(lg[-1])))
    gaps, lg = reference.served_gaps(a, w, prompt, served)
    assert gaps.shape == (6,) and lg.shape == (6, 32)
    np.testing.assert_allclose(gaps, 0.0, atol=1e-6)
    other = [(t + 1) % 32 for t in served]
    assert reference.served_gaps(a, w, prompt, other)[0].min() > 0

    # window 3, d 8, one head of 8, 2 layers, vocabulary 32: a layer's
    # matmuls 64 parameters, a token's K/V 16 bytes, the head 512 FLOPs
    adm = work.admission(a, [{"offset": 5, "length": 4, "group": None}])
    # the 4 queries attend 3 tokens each; K/V: the 4 own and 2 of the
    # prefix, the ones the first query reaches
    assert adm.attn_flops == 4 * 8 * 12 * 2
    assert adm.flops == 2 * 64 * 4 * 2 + 4 * 8 * 12 * 2 + 512
    assert adm.attn_bytes == 2 * 4 * 8 * 2 * 2 + (4 + 2) * 16 * 2
    step = work.decode_step(a, [{"context": 10, "shared": 8, "group": 1},
                                {"context": 9, "shared": 8, "group": 1}])
    # each attends 3; of the shared prefix the two read 1 and 2 tokens,
    # the second's covering the first's: 2 + 1 own + 2 shared
    assert step.attn_flops == 2 * 4 * 8 * 3 * 2
    assert step.attn_bytes == 2 * (2 * 8 * 2 * 2) + 5 * 16 * 2
    seg = work.segment(a, [{"context0": 4, "emitted": 2, "shared": 0,
                            "group": None}])
    assert seg.attn_flops == 2 * 4 * 8 * 3 * 2
    assert files(C.HERE) == before


def test_smoke_widths_come_from_the_configuration_file():
    real = C.load_json(os.path.join(C.HERE, "configs", "qwen2-7b.json"))
    conf = smoke.smoke_cell("qwen2-7b.chat").config
    assert real["smoke"]["num_hidden_layers"] == 2
    for k, v in real["smoke"].items():
        assert conf[k] == v
    a = C.arch(conf)
    assert (a.d, a.n_heads, a.n_kv, a.ff, a.vocab, a.layers) == tuple(
        real["smoke"][k] for k in ("hidden_size", "num_attention_heads",
                                   "num_key_value_heads",
                                   "intermediate_size", "vocab_size",
                                   "num_hidden_layers"))
    C.program_config(conf, smoke=True)      # the program's SMOKE agrees


def test_a_traffic_file_hides_a_stand_in_of_the_same_name(monkeypatch):
    stand_in = dict(smoke.STAND_IN_MIXES["sessions"], why="a stand-in")
    monkeypatch.setitem(smoke.STAND_IN_MIXES, "chat", stand_in)
    chat = C.load_json(os.path.join(C.HERE, "traffic", "chat.json"))
    got = smoke.smoke_cell("qwen2-7b.chat").traffic
    assert got["why"] == chat["why"] and "sessions" not in got
    # with no file of its name, the stand-in serves
    assert "sessions" in smoke.smoke_cell("qwen2-7b.sessions").traffic
