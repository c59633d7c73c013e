"""What one cell is made of, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
configuration is ``configs/<config>.json``, the mix ``traffic/<mix>.json``,
the configuration's architecture ``archs/<model_type>.py``, and each
per-layer metric's reader ``metrics/<name>.py``.  Adding a cell, a mix, a
metric or an architecture adds files and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

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
        traffic=load_json(os.path.join(root, "chipbench", "traffic",
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
    return _module(path, "chipbench_metric_" + name.replace(".", "_"))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod       # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ architecture
_PLAIN = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*")


def arch_plugin(model_type: str, root: str = ROOT):
    """The module ``archs/<model_type>.py``, found from a configuration's
    ``model_type`` as a metric's reader is found from its name.  It holds
    what one architecture variant needs and nothing shared:

    - ``arch(config) -> Arch``: the shape, read from the configuration
      file, refusing keys its equations do not cover.  ``Arch`` is a
      hashable (frozen) dataclass, a static argument of the jitted draw
      and reference, with ``d``, ``vocab``, ``layers``, ``n_heads``, ``hd``
      and ``rope_theta`` and these methods:
    - ``draw(key)``: every parameter in the published layout, ``embed``
      (vocabulary rows) and ``layers`` (stacked on a leading axis) among
      them, built on ``weights.py``'s helpers; ``to_program(w)``: the
      program's parameter tree for them;
    - ``layer(lw, x, pos)`` and ``logits(w, h)``: one decoder layer on
      x: (S, d) and the head on rows h, in float32 at ``highest``
      precision (``reference.mm``);
    - ``layer_matmul_params()``, ``kv_token_bytes()`` and
      ``attended(context)``: the work counts (``work.py``);
    - ``check_program(cfg)``: each field on which the program's own
      ArchConfig differs from the file, ``name -> (program's, file's)``.
    """
    if not _PLAIN.fullmatch(model_type):
        raise ValueError(f"model_type {model_type!r} is not a plain name")
    path = os.path.join(root, "chipbench", "archs", model_type + ".py")
    name = "chipbench_arch_" + model_type
    # loaded once, so that its Arch class, a jit cache key, stays one
    # object within a process
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != path:
        if not os.path.exists(path):
            raise ValueError(f"no plug-in for model_type {model_type!r}: "
                             f"{path} not found")
        mod = _module(path, name)
    return mod


def arch(config: dict, root: str = ROOT):
    """The configuration's ``Arch``, from its ``model_type``'s plug-in."""
    return arch_plugin(config["model_type"], root).arch(config)


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
    off = a.check_program(cfg)
    if off:
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"config differs from the file (program's, file's): "
                         f"{off}")
    return cfg
