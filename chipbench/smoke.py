"""Smoke-width stand-ins of the benchmark's cells, for the CPU tests.

A smoke cell keeps a real cell's configuration file and traffic mix but
takes the program's SMOKE widths (2 layers, a 256-token vocabulary), a
small plan and short lengths, so that the whole run path (set-up, window,
reference) runs on the CPU with the Pallas kernels in interpret mode.

Two stand-ins cover what no cell of BENCHMARK.json has a file for yet: the
starcoder2 variant of the model code and the reference (LayerNorm, a GELU
MLP with biases, a tied head), and a mix with Zipf-picked sessions whose
prefixes are filled before the window.
"""

from __future__ import annotations

from chipbench import cell as C

WIDTHS = {
    "qwen2-7b": dict(hidden_size=56, num_attention_heads=7,
                     num_key_value_heads=1, intermediate_size=96,
                     vocab_size=256, num_hidden_layers=2),
    "starcoder2-3b": dict(hidden_size=64, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=128,
                          vocab_size=256, num_hidden_layers=2),
}
STAND_IN_CONFIGS = {
    "starcoder2-3b": {"name": "starcoder2-3b", "arch": "starcoder2-3b",
                      "model_type": "starcoder2", "norm_epsilon": 1e-05,
                      "use_bias": True, "tie_word_embeddings": True,
                      "rope_theta": 999999.4420358813},
}
STAND_IN_MIXES = {
    "repo": {"arrivals": {"kind": "poisson"}, "drain_s": 30,
             "sessions": {"count": 3, "zipf": 1.1,
                          "prefix": {"median": 48, "sigma": 0.3, "min": 32,
                                     "max": 64, "align": 16}},
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
    the files (or the stand-ins above) are read directly, so a
    configuration or mix that no cell of BENCHMARK.json uses is tested too.
    ``arrivals`` replaces the mix's arrival law by another kind
    (``offline``) at the same rate.  The cell reports every end-to-end
    metric the harness computes and every per-layer metric of
    BENCHMARK.json."""
    config, traffic = name.rsplit(".", 1)
    real = (STAND_IN_CONFIGS.get(config)
            or C.load_json(f"{C.HERE}/configs/{config}.json"))
    conf = dict(real, **WIDTHS[config], plan=dict(PLAN),
                correct={"max_gap": MAX_GAP})
    mix = dict(STAND_IN_MIXES.get(traffic)
               or C.load_json(f"{C.HERE}/traffic/{traffic}.json"))
    mix["prompt"] = {"median": 24, "sigma": 0.5, "min": 8, "max": 32}
    mix["output"] = {"median": 8, "sigma": 0.5, "min": 4, "max": 16}
    kind = arrivals or mix["arrivals"]["kind"]
    key = "rate_per_s" if kind == "poisson" else "requests_per_s"
    mix["arrivals"] = {"kind": kind, key: rate_per_s}
    mix["sample_tokens"] = 24
    return C.Cell(name=name, config=conf, traffic=mix, chips=1,
                  end_to_end=END_TO_END,
                  per_layer=tuple(C.benchmark()["per_layer"]))
