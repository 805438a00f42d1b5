"""Fig. 1: drafting vs verification time per SpecPV step as context grows.

The paper's motivating measurement: with an EAGLE-3-style draft, the
verification share of step time grows with context length.  We time the
draft phase (draft_extend + tree_draft) and the full-verification forward
separately on the trained tiny model across context lengths.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import RESULTS_DIR, print_table, time_fn, write_rows  # noqa

from repro.artifacts import get_trained_pair, corpus_for  # noqa
from repro.configs import SpecPVConfig  # noqa
from repro.core import SpecPVEngine  # noqa
from repro.core import draft as dr  # noqa
from repro.core import verify as vf  # noqa
from repro.data import continuation_task  # noqa
from repro.models import api  # noqa


def main(quick: bool = False):
    cfg, dcfg, params, dparams = get_trained_pair("tiny-dense")
    corpus = corpus_for(cfg)
    spec = SpecPVConfig(block_size=16, num_sink_blocks=1,
                        retrieval_budget_blocks=4, local_window_blocks=2,
                        buffer_size=48)
    contexts = [128, 256] if quick else [128, 256, 512, 1024]
    rows = []
    for ctx in contexts:
        eng = SpecPVEngine(cfg, spec, dcfg, params, dparams, batch=1,
                           max_len=ctx + 256, partial_verification=False)
        prompt, _ = continuation_task(corpus, batch=1, context_len=ctx)
        st = eng.prefill(prompt, chunk=128)

        tree = eng.tree

        @jax.jit
        def draft_only(params, dparams, st):
            ext_valid = (jnp.arange(eng.emax)[None] < st.ext_len[:, None])
            dcache, h_root, lg = dr.draft_extend(
                cfg, dcfg, dparams, params, st.dcache, st.ext_tokens,
                st.ext_feats, ext_valid)
            return dr.tree_draft(cfg, dcfg, dparams, params, dcache, tree,
                                 h_root, lg, st.ext_tokens[:, 0])

        tree_tokens, _ = draft_only(params, dparams, st)

        @jax.jit
        def verify_only(params, st, tree_tokens):
            vin = vf.build_verify_inputs(tree, st.pending[:, :1],
                                         jnp.ones((1,), jnp.int32),
                                         tree_tokens, st.seq_len)
            out = api.decode(cfg, params, vin["tokens"], vin["positions"],
                             st.cache, mode="full",
                             self_mask=vin["self_mask"], spec=spec)
            return out.logits

        t_draft = time_fn(draft_only, params, dparams, st, iters=3)
        t_verify = time_fn(verify_only, params, st, tree_tokens, iters=3)
        frac = t_verify / (t_draft + t_verify)
        rows.append([ctx, f"{t_draft*1e3:.1f}", f"{t_verify*1e3:.1f}",
                     f"{frac:.2f}"])
    header = ["context", "draft_ms", "verify_ms", "verify_fraction"]
    print_table("Fig.1 — draft vs verification time", header, rows)
    write_rows(os.path.join(RESULTS_DIR, "fig1_bottleneck.csv"), header,
               rows)
    for r in rows:
        print(f"fig1/ctx{r[0]},{float(r[2])*1e3:.0f},"
              f"verify_frac={r[3]}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
