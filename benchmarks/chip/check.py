"""The comparison that decides ``correct``.

The reference (``references/<config reference>.py``) runs once the
window has closed, the peak memory has been read and the engine has been
freed, over each sampled request's prompt followed by the tokens the
engine served, one request at a time and padded to one shape so that it
compiles once.  It is given weights drawn again from the seed by
``weights.py``: nothing the program made.

Two kinds, named by a configuration's ``check`` key:

* ``greedy_gap`` (exact substrates, greedy traffic): at every served
  position, how far the served token's reference logit lies below the
  reference's best.  A sound engine serves the reference's argmax up to
  near-ties, so the widest gap stays at the size of rounding; a wrong or
  altered token lies a logit spread below.
* ``sc_cosine`` (the SC substrate): the mean cosine between the engine's
  logits, as sampled, and the reference's, which must lie inside a band:
  below it the stochastic products are noisier than the configured bits
  allow, above it they are not stochastic at all.  Each served token
  must also be the argmax of the logits it was sampled from (greedy).

The control (``quant``: the configuration's ``control`` precision) puts
the reference computed in that lower precision in the program's place
at the same positions: the token it puts first (``greedy_gap``), or its
logits (``sc_cosine``), are judged in the same way, under the same
limits.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np


def reference_module(config: dict):
    return importlib.import_module(
        f"benchmarks.chip.references.{config['reference']}")


def sample(served, seed: int, target_tokens: int) -> list:
    """Requests drawn from the seed among those that served tokens in
    the window, the longest first, until ``target_tokens`` served tokens
    are covered.  A request still running when the window closed counts
    with the tokens it served: in a saturated cell few requests finish
    inside a window, and a served token is final when it is served."""
    if not served:
        return []
    order = list(np.random.default_rng(seed).permutation(len(served)))
    longest = max(range(len(served)),
                  key=lambda i: len(served[i].generated))
    order.remove(longest)
    out, tokens = [], 0
    for i in [longest] + order:
        out.append(served[i])
        tokens += len(served[i].generated)
        if tokens >= target_tokens:
            break
    return out


def _pad(req, pad_len: int, max_new: int):
    seq = list(req.prompt) + list(req.generated)
    n = len(req.generated)
    tokens = np.zeros(pad_len, np.int32)
    tokens[:len(seq)] = seq
    pos = np.zeros(max_new, np.int32)
    pos[:n] = len(req.prompt) - 1 + np.arange(n)
    served = np.zeros(max_new, np.int32)
    served[:n] = req.generated
    mask = np.arange(max_new) < n
    return tokens, pos, served, mask


@functools.partial(jax.jit, static_argnames=("ref", "c", "quant"))
def _gaps(params, tokens, pos, served, *, ref, c, quant):
    c = dict(c)
    lg = ref.logits(params, ref.hidden(params, tokens, c)[pos], c)
    if quant is not None:
        hq = ref.hidden(params, tokens, c, quant)
        served = jnp.argmax(ref.logits(params, hq[pos], c, quant), -1)
    return lg.max(-1) - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames=("ref", "c", "quant"))
def _cosines(params, tokens, pos, eng, *, ref, c, quant):
    c = dict(c)
    lg = ref.logits(params, ref.hidden(params, tokens, c)[pos], c)

    if quant is not None:
        hq = ref.hidden(params, tokens, c, quant)
        eng = ref.logits(params, hq[pos], c, quant)
    return (eng * lg).sum(-1) / (jnp.linalg.norm(eng, axis=-1)
                                 * jnp.linalg.norm(lg, axis=-1))


class _Frozen(dict):
    """A configuration dict that can be a static jit argument: hashed by
    its scalar entries (the sizes)."""

    def __hash__(self):
        return hash(tuple(sorted(
            (k, v) for k, v in self.items()
            if isinstance(v, (int, float, bool, str)))))


def greedy_gap(params, config, reqs, pad_len, max_new, quant=None) -> dict:
    ref, c = reference_module(config), _Frozen(config)
    gaps = []
    for r in reqs:
        tokens, pos, served, mask = _pad(r, pad_len, max_new)
        g = _gaps(params, tokens, pos, served, ref=ref, c=c, quant=quant)
        gaps.append(float(np.asarray(g)[mask].max()))
    return {"max_gap": max(gaps), "per_request": gaps}


def sc_cosine(params, config, reqs, logits, pad_len, max_new,
              quant=None) -> dict:
    """``logits``: rid -> (n, vocab) engine logits of the served tokens.
    The control serves its own first choices: it has no mismatch to
    count."""
    ref, c = reference_module(config), _Frozen(config)
    cosines, mismatches = [], 0
    for r in reqs:
        tokens, pos, _, mask = _pad(r, pad_len, max_new)
        eng = np.asarray(logits[r.rid], np.float32)
        n = len(r.generated)
        mismatches += int((eng.argmax(-1) != np.asarray(r.generated)).sum())
        full = np.zeros((max_new, eng.shape[1]), np.float32)
        full[:n] = eng
        cs = _cosines(params, tokens, pos, full, ref=ref, c=c, quant=quant)
        cosines += np.asarray(cs)[mask].tolist()
    out = {"cos_mean": float(np.mean(cosines)), "positions": len(cosines)}
    if quant is None:
        out["argmax_mismatch"] = mismatches
    return out
