"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole run past the device check (smoke widths, Pallas
in interpret mode) with the program broken underneath in one way a serving
cell can break, and sees ``correct`` come out false: a decode segment that
returns its KV state unchanged, half of the batch left out, and a token
altered where it is produced.  (An exchange between chips does not exist
in a one-chip cell.)"""

import jax
import jax.numpy as jnp

from chipbench.run import Compiles, run_cell
from chipbench.peaks import PEAKS
from chipbench.smoke import smoke_cell


def run_broken(break_engine):
    return run_cell(smoke_cell("qwen2-7b.chat"), 2**31 + 21, 1.5, False,
                    peak=PEAKS["TPU v5 lite"],
                    device={"platform": "cpu", "kind": "cpu", "count": 1,
                            "compiles": Compiles()},
                    smoke=True, interpret=True, break_engine=break_engine)


def rejit_segment(engine, fn):
    engine._segment = jax.jit(fn, donate_argnums=(1,))


def test_segment_that_leaves_the_kv_pool_unchanged():
    def broken(engine):
        impl = engine._segment_impl

        def segment(params, cache, *rest):
            out = impl(params, cache, *rest)
            return (dict(out[0], blocks=cache["blocks"]),) + out[1:]
        rejit_segment(engine, segment)
    assert run_broken(broken)["correct"] is False


def test_half_of_the_batch_left_out():
    def broken(engine):
        impl = engine._segment_impl

        def segment(params, cache, tok, active, *rest):
            out = impl(params, cache, tok, active, *rest)
            toks = out[4]
            half = toks.shape[1] // 2
            # only the second half of the slots is computed; the first
            # half gets its tokens
            toks = jnp.concatenate([toks[:, half:], toks[:, half:]], axis=1)
            return out[:4] + (toks,) + out[5:]
        rejit_segment(engine, segment)
    assert run_broken(broken)["correct"] is False


def test_token_altered_where_it_is_produced():
    def broken(engine):
        impl = engine._segment_impl
        vocab = engine.model.cfg.vocab_size

        def segment(*args):
            out = impl(*args)
            return out[:4] + ((out[4] + 1) % vocab,) + out[5:]
        rejit_segment(engine, segment)
    assert run_broken(broken)["correct"] is False
