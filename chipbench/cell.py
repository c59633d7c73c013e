"""What one cell is made of, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
configuration is ``configs/<config>.json``, the mix ``traffic/<mix>.json``,
and each per-layer metric's reader ``metrics/<name>.py``.  Adding a cell,
a mix or a metric adds files and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple      # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(HERE, "traffic",
                                       w["traffic"] + ".json")),
        chips=int(w["chips"]),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def metric_reader(name: str):
    """The module ``metrics/<name>.py``: ``read(record) -> float | None``
    (``None`` where the record holds nothing to read).  A metric split by
    the end-to-end metric it moves, ``<base>.<part>``, is read by
    ``metrics/<base>.py`` unless it has a file of its own.  Its layer, unit
    and the end-to-end metric it moves are stated once, in BENCHMARK.json."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ architecture
@dataclasses.dataclass(frozen=True)
class Arch:
    """The published shape of a dense decoder, read from its config file:
    what the weight draw, the reference and the work counts need."""
    name: str
    d: int
    n_heads: int
    n_kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    norm: str            # "rms" | "layer"
    eps: float
    glu: bool            # SwiGLU (gate, up, down) vs GELU-tanh (fc, proj)
    qkv_bias: bool
    mlp_bias: bool
    tied: bool
    rope_theta: float


def arch(config: dict) -> Arch:
    kind = config["model_type"]
    if kind == "qwen2":
        norm, eps, glu, qkv_b, mlp_b = ("rms", config["rms_norm_eps"], True,
                                        True, False)
    elif kind == "starcoder2":
        norm, eps, glu = "layer", config["norm_epsilon"], False
        qkv_b = mlp_b = bool(config["use_bias"])
    else:
        raise ValueError(f"no reference for model_type {kind!r}")
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Arch(name=config["name"], d=d, n_heads=h,
                n_kv=config["num_key_value_heads"],
                hd=config.get("head_dim", d // h),
                ff=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"], norm=norm,
                eps=float(eps), glu=glu, qkv_bias=qkv_b, mlp_bias=mlp_b,
                tied=bool(config["tie_word_embeddings"]),
                rope_theta=float(config["rope_theta"]))


def program_config(config: dict, smoke: bool = False):
    """The program's ArchConfig for this configuration: the registry's
    entry with the file's depth and rope_theta and the served dtype.  Every
    width must agree with the file; a registry that drifts from the
    published widths is an error, not a silent change of cell."""
    from repro.configs.registry import get_config
    a = arch(config)
    cfg = get_config(config["arch"], smoke=smoke).replace(
        n_layers=a.layers, rope_theta=a.rope_theta,
        param_dtype="bfloat16", act_dtype="bfloat16")
    have = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab_size, cfg.tie_embeddings, cfg.qkv_bias,
            cfg.norm == ("rmsnorm" if a.norm == "rms" else "layernorm"),
            cfg.mlp == ("glu" if a.glu else "gelu_mlp"),
            bool(cfg.mlp_bias) == a.mlp_bias)
    want = (a.d, a.n_heads, a.n_kv, a.hd, a.ff, a.vocab, a.tied,
            a.qkv_bias, True, True, True)
    if have != want:
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"config {have} differs from the file {want}")
    return cfg
