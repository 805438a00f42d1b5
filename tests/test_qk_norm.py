"""Qwen3's per-head q/k RMSNorm through the serving model, against the
plain Qwen3 reference (``bench/refs/qwen3.py``).

A tiny Qwen3 — dense, GQA 4/2, head_dim 16, float32 — whose ``q_norm``
and ``k_norm`` scales are drawn N(1, 0.2), so that a dropped or swapped
scale moves the logits.  Through the model API on a paged cache whose
pages are shuffled:

* (a) chunked prefill, then a decode step that reads the paged cache,
  gives the reference's ``forward`` logits at every position;
* (b) a PARTIAL verify after a refresh, over the blocks the refresh
  gathered or routed to, gives the reference's ``segment`` logits over
  the same selection;
* (c) the refresh selects what the reference's ``select`` picks from its
  normalised keys and queries;
* (d) the same program with ``k_norm`` or ``q_norm`` dropped, or the two
  scales swapped, passes neither (a) nor (c).

Weights without the norm leaves (every dense model the repository
served before) trace ``project_q`` / ``project_kv`` to the jaxpr the
functions gave before the norm was added (copied below).

Both sides compute in float32, so the tolerance (``ATOL``) only has to
cover a different order of summation: the program's chunked online
softmax and fused projections against the reference's plain einsums.
"""
import dataclasses
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ModelConfig, SpecPVConfig
from repro.models import api
from repro.models import blocks as bk
from repro.models import common as cm
from repro.models import dense as dn

REF_PATH = Path(__file__).resolve().parents[1] / "bench" / "refs" / "qwen3.py"

CFG = ModelConfig(name="tiny-qwen3", arch_type="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                  d_ff=128, vocab_size=256, norm_eps=1e-6,
                  rope_theta=1_000_000.0)
# budget 1 + 2 + 2 blocks of 16: a 160-token context leaves 7 candidate
# blocks for 2 retrieval slots, so the scores decide the selection
SPEC = SpecPVConfig(block_size=16, num_sink_blocks=1,
                    retrieval_budget_blocks=2, local_window_blocks=2,
                    buffer_size=48)
PROMPT, CHUNK, NEW = 160, 64, 32          # prefill length, chunk, decoded
REFRESH = 4                               # refresh queries after PROMPT
SERVED = ("qwen3", "k_norm_dropped", "q_norm_dropped", "scales_swapped")
# logits are of unit scale (untied head N(0, 1/d)); float32 summation
# order moves them by ~3e-6 here, and one dropped or swapped norm scale
# by ~2, so 1e-4 holds the first with room and fails the second
ATOL = 1e-4


def load_reference():
    spec = importlib.util.spec_from_file_location("ref_qwen3_tests",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return load_reference()


@pytest.fixture(scope="module")
def weights():
    params = dn.init_params(CFG, jax.random.PRNGKey(7))
    attn = params["decoder"]["slots"][0]["attn"]
    shape = (CFG.num_layers, CFG.head_dim)
    for i, name in enumerate(("q_norm", "k_norm")):
        attn[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(100 + i), shape, jnp.float32)
    return params


@pytest.fixture(scope="module")
def dm():
    return SimpleNamespace(layers=CFG.num_layers, heads=CFG.num_heads,
                           kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim,
                           eps=CFG.norm_eps, theta=CFG.rope_theta,
                           tied=CFG.tie_embeddings)


@pytest.fixture(scope="module")
def seq():
    rng = np.random.default_rng(11)
    return rng.integers(0, CFG.vocab_size, PROMPT + NEW).astype(np.int32)


@pytest.fixture(scope="module")
def reference_run(ref, dm, weights, seq):
    """The reference over the whole sequence: logits at every position,
    and each layer's keys, values and the refresh's queries."""
    q_idx = np.arange(PROMPT, PROMPT + REFRESH)
    h, ks, vs, qs = ref.forward(dm, weights, seq, q_idx)
    return ref.head(dm, weights, h), ks, vs, qs


def served_variant(params, served):
    params = jax.tree_util.tree_map(lambda a: a, params)   # fresh dicts
    attn = params["decoder"]["slots"][0]["attn"]
    if served == "k_norm_dropped":
        del attn["k_norm"]
    elif served == "q_norm_dropped":
        del attn["q_norm"]
    elif served == "scales_swapped":
        attn["q_norm"], attn["k_norm"] = attn["k_norm"], attn["q_norm"]
    return params


def prefilled_cache(params, seq):
    """The prompt prefilled in chunks into a paged cache whose logical
    blocks sit on shuffled pages; returns (prompt logits, cache)."""
    nb = (PROMPT + NEW) // SPEC.block_size
    cache = api.init_cache(CFG, 1, PROMPT + NEW, SPEC, paged=True,
                           num_pages=nb + 1)
    pages = np.random.default_rng(3).permutation(nb) + 1
    cache["page_table"] = jnp.asarray(pages[None], jnp.int32)
    logits = []
    for c0 in range(0, PROMPT, CHUNK):
        chunk = seq[None, c0:min(c0 + CHUNK, PROMPT)]
        lg, _, cache = api.prefill(CFG, params, jnp.asarray(chunk), cache,
                                   spec=SPEC, return_logits="all")
        logits.append(np.asarray(lg[0]))
    return np.concatenate(logits), cache


def positions(start, n):
    return jnp.arange(start, start + n, dtype=jnp.int32)[None]


@pytest.mark.parametrize("served", SERVED)
def test_prefill_then_paged_decode_matches_forward(weights, seq,
                                                   reference_run, served):
    """(a) as served; (d) with a norm dropped or the scales swapped."""
    params = served_variant(weights, served)
    pre, cache = prefilled_cache(params, seq)
    out = api.decode(CFG, params, jnp.asarray(seq[None, PROMPT:]),
                     positions(PROMPT, NEW), cache, mode="full", spec=SPEC)
    got = np.concatenate([pre, np.asarray(out.logits[0])])
    err = np.max(np.abs(got - reference_run[0]))
    if served == "qwen3":
        assert err < ATOL, err
    else:
        assert err > 100 * ATOL, err


def refresh(params, seq, cache):
    """A refresh verify of the REFRESH tokens after the prompt: full
    attention, and the partial context selected from its queries."""
    out = api.decode(CFG, params, jnp.asarray(seq[None, PROMPT:PROMPT +
                                                  REFRESH]),
                     positions(PROMPT, REFRESH), cache, mode="full",
                     spec=SPEC, select_partial=True)
    return out.partial                    # (pk, pv, ppos) [L, 1, Hk, P, ..]


def selected_blocks(ppos):
    """[L, Hk, NS] logical block ids of a gathered partial cache, -1 for
    an unused retrieval slot."""
    pos = np.asarray(ppos)[:, 0]
    first = pos.reshape(pos.shape[:2] + (-1, SPEC.block_size))[..., 0]
    return np.where(first >= 0, first // SPEC.block_size, -1)


@pytest.fixture(scope="module")
def refreshed(weights, seq):
    _, cache = prefilled_cache(weights, seq)
    return cache, refresh(weights, seq, cache)


@pytest.mark.parametrize("served", SERVED)
def test_refresh_selects_reference_blocks(ref, dm, weights, seq,
                                          reference_run, served):
    """(c) the program's selection, per layer and kv head, is the
    reference's from its normalised keys and the refresh's queries; with
    a norm dropped or the scales swapped it is not."""
    _, ks, _, qs = reference_run
    want = ref.select_layers(dm, dataclasses.asdict(SPEC), ks, qs, PROMPT)
    params = served_variant(weights, served)
    _, cache = prefilled_cache(params, seq)
    got = selected_blocks(refresh(params, seq, cache)[2])
    same = [sorted(got[layer, head]) == sorted(want[layer][head])
            for layer in range(CFG.num_layers)
            for head in range(CFG.num_kv_heads)]
    assert all(same) == (served == "qwen3"), got


@pytest.mark.parametrize("route", ["gathered", "routed"])
def test_partial_verify_matches_segment(ref, dm, weights, seq,
                                        reference_run, refreshed, route):
    """(b) PARTIAL rows after the refresh attend the selected blocks and
    their own run: the reference's ``segment`` over the selection the
    program made.  ``routed`` reads the blocks in place through the
    page table (zero-copy) with an empty tail buffer."""
    cache, partial = refreshed
    sel = selected_blocks(partial[2])
    toks = seq[PROMPT:PROMPT + NEW // 2]
    kw = {"pkv": partial}
    if route == "routed":
        pk, pv, ppos = partial
        empty = jnp.full(ppos.shape[:3] + (SPEC.block_size,), -1, jnp.int32)
        kw = {"pkv": (pk[..., :SPEC.block_size, :],
                      pv[..., :SPEC.block_size, :], empty),
              "pkv_blocks": jnp.asarray(sel[:, None], jnp.int32)}
    out = api.decode(CFG, weights, jnp.asarray(toks[None]),
                     positions(PROMPT, len(toks)), cache, mode="partial",
                     spec=SPEC, **kw)
    _, ks, vs, _ = reference_run
    h = ref.segment(dm, weights, ks, vs, dataclasses.asdict(SPEC),
                    list(sel), PROMPT, toks, PROMPT)
    want = ref.head(dm, weights, h)
    err = np.max(np.abs(np.asarray(out.logits[0]) - want))
    assert err < ATOL, err


# ---------------------------------------------------------------------------
# weights without q/k norm: the projections trace as before the norm
# ---------------------------------------------------------------------------

def project_q_before(cfg, p, x, positions, inv_freq, mscale):
    """``blocks.project_q`` as it was before Qwen3's q norm."""
    b, t, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(b, t, cfg.num_heads, cfg.head_dim_)
    return cm.apply_rope(q, positions, inv_freq, mscale)


def project_kv_before(cfg, p, x, positions, inv_freq, mscale, *,
                      rope=True):
    """``blocks.project_kv`` as it was before Qwen3's k norm."""
    b, t, _ = x.shape
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(b, t, cfg.num_kv_heads, cfg.head_dim_)
    v = v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim_)
    if rope:
        k = cm.apply_rope(k, positions, inv_freq, mscale)
    return k, v


@pytest.mark.parametrize("bias", [False, True], ids=["plain", "qkv_bias"])
@pytest.mark.parametrize("proj", ["q", "kv"])
def test_weights_without_norm_trace_as_before(proj, bias):
    cfg = CFG.replace(dtype="bfloat16", param_dtype="bfloat16",
                      qkv_bias=bias)
    p = bk.init_attn_params(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((2, 8, cfg.d_model), jnp.bfloat16)
    pos = jnp.zeros((2, 8), jnp.int32)
    inv = jnp.asarray(cm.rope_inv_freq(cfg))
    now, before = ((bk.project_q, project_q_before) if proj == "q"
                   else (bk.project_kv, project_kv_before))

    def jaxpr(fn):
        return str(jax.make_jaxpr(
            lambda p, x, pos: fn(cfg, p, x, pos, inv, 1.0))(p, x, pos))

    assert jaxpr(now) == jaxpr(before)
