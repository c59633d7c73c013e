"""Smoke-width stand-ins of the benchmark's cells, for the CPU tests.

A smoke cell keeps a real cell's configuration file and traffic mix but
takes the configuration's own smoke widths (its file's ``smoke`` key: the
program's SMOKE widths, 2 layers and a 256-token vocabulary), a small plan
and short lengths, so that the whole run path (set-up, window, reference)
runs on the CPU with the Pallas kernels in interpret mode.

One stand-in mix covers what no cell of BENCHMARK.json has a file for yet:
Zipf-picked sessions whose prefixes are filled before the window.  A mix
file of the same name comes first.
"""

from __future__ import annotations

import os

from chipbench import cell as C

STAND_IN_MIXES = {
    "sessions": {"arrivals": {"kind": "poisson"}, "drain_s": 30,
                 "sessions": {"count": 3, "zipf": 1.1,
                              "prefix": {"median": 48, "sigma": 0.3,
                                         "min": 32, "max": 64, "align": 16}},
                 "plan": {"retain_pages": 40}},
}
PLAN = {"slots": 4, "pool_tokens": 1024, "max_prompt": 96, "max_output": 16,
        "prefill_bucket": 32}
# widest gap at smoke widths: sound runs read up to about 0.05 logits
MAX_GAP = 0.1
END_TO_END = ({"name": "ttft_p80_ms", "unit": "ms"},
              {"name": "tpot_p80_ms", "unit": "ms"},
              {"name": "output_tok_per_s", "unit": "tokens/s"},
              {"name": "setup_s", "unit": "s"})


def smoke_cell(name: str, rate_per_s: float = 4.0,
               arrivals: str | None = None) -> C.Cell:
    """``name`` is ``<config>.<mix>``, as a cell of BENCHMARK.json is named;
    the files (or the stand-in mix above) are read directly, so a
    configuration or mix that no cell of BENCHMARK.json uses is tested too.
    ``arrivals`` replaces the mix's arrival law by another kind
    (``offline``) at the same rate.  The cell reports every end-to-end
    metric the harness computes and every per-layer metric of
    BENCHMARK.json."""
    config, traffic = name.rsplit(".", 1)
    real = C.load_json(f"{C.HERE}/configs/{config}.json")
    conf = dict(real, **real["smoke"], plan=dict(PLAN),
                correct={"max_gap": MAX_GAP})
    path = f"{C.HERE}/traffic/{traffic}.json"
    mix = dict(C.load_json(path) if os.path.exists(path)
               else STAND_IN_MIXES[traffic])
    mix["prompt"] = {"median": 24, "sigma": 0.5, "min": 8, "max": 32}
    mix["output"] = {"median": 8, "sigma": 0.5, "min": 4, "max": 16}
    kind = arrivals or mix["arrivals"]["kind"]
    key = "rate_per_s" if kind == "poisson" else "requests_per_s"
    mix["arrivals"] = {"kind": kind, key: rate_per_s}
    mix["sample_tokens"] = 24
    return C.Cell(name=name, config=conf, traffic=mix, chips=1,
                  end_to_end=END_TO_END,
                  per_layer=tuple(C.benchmark()["per_layer"]))
