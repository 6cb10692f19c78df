"""One general traffic generator.  A mix is a JSON file of parameters
under ``benchmark/traffic/``; everything drawn comes from ``--seed``.

Serving mixes (``"kind": "serve"``)::

    {"arrivals": {"gaps": "exponential", "rate_per_s": 3.0},
     "prompt_tokens": {"dist": "lognormal", "mean": 161.31, "sigma": 0.93,
                       "min": 4, "max": 1024},
     "output_tokens": {...same keys...},
     "lead_s": 10.0, "base_seed": 20260930}

``mean`` is the mean of the log-normal before it is clipped to
``min..max`` (its median is ``mean / exp(sigma**2 / 2)``).

Every seed sees the SAME cycle of lengths and of inter-arrival gaps:
``round(rate x seconds)`` requests, drawn once from the mix's own
``base_seed``, the gaps scaled so that the cycle lasts ``seconds``.  The
run is that cycle repeated without end: the window holds one whole cycle,
the lead-in (``lead_s``) is the end of the cycle before it and the
lead-out (``tail_s``, offered while the window's requests are followed to
their end) the start of the next.  The seed draws the token ids (and the
weights), never the amount of work, its order or its timing.  It is one
fixed realisation of exponential gaps, NOT a fresh Poisson draw per seed
(PERF.md section 4 says why).

Training mixes (``"kind": "train"``) need only ``batch`` and ``seq``:
:func:`train_tokens` draws a pool of token rows, all different.
"""
import numpy as np


def _rng(*ints):
    return np.random.default_rng([int(i) % (2 ** 63) for i in ints])


def _lengths(spec, n, rng):
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    sigma = float(spec["sigma"])
    median = float(spec["mean"]) / np.exp(sigma ** 2 / 2)
    x = rng.lognormal(np.log(median), sigma, n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _gaps(arr, n, rng):
    if arr["gaps"] != "exponential":
        raise ValueError(f"unknown inter-arrival gaps {arr['gaps']!r}")
    return rng.exponential(1.0 / float(arr["rate_per_s"]), n)


def serve_schedule(mix, seed, seconds, vocab):
    """Requests due from ``-lead_s`` to ``seconds + tail_s``: a list of
    dicts with ``due`` (seconds from the window's opening; negative =
    lead-in, ``>= seconds`` = lead-out), ``prompt`` (token ids) and
    ``max_new_tokens``, ordered by ``due``.  The window holds the whole
    cycle; what comes before and after it is the same cycle again.  Near
    capacity it is the ORDER of long requests and short gaps that makes
    or spares a transient queue: with lengths and gaps drawn freely from
    the seed TTFT p95 read anywhere between 80 and 510 ms on four seeds
    (the first chat mix at 9.6 req/s), and with the cycle merely started
    at a request of the seed's choosing TPOT p95 still read in two modes,
    20.6-20.9 and 21.9-22.1 ms (my chip runs, PR 24)."""
    seconds = float(seconds)
    lead, tail = float(mix.get("lead_s", 0.0)), float(mix.get("tail_s", 0.0))
    n = int(round(float(mix["arrivals"]["rate_per_s"]) * seconds))
    if n <= 0:
        return []
    base = _rng(mix.get("base_seed", 0), 11, n)
    gaps = _gaps(mix["arrivals"], n, base)
    plen = _lengths(mix["prompt_tokens"], n, base)
    olen = _lengths(mix["output_tokens"], n, base)
    gaps = gaps * (seconds / gaps.sum())
    # request i of a cycle is due after its first i gaps: the first at once
    start = np.minimum(np.cumsum(gaps) - gaps, seconds * (1 - 1e-9))
    toks = _rng(seed, 11, 2)
    out = []
    for cycle in range(-int(np.ceil(lead / seconds)),
                       int(np.ceil(tail / seconds)) + 1):
        for i in range(n):
            due = cycle * seconds + float(start[i])
            if -lead <= due < seconds + tail:
                out.append({"due": due,
                            "prompt": toks.integers(0, vocab, int(plen[i]),
                                                    dtype=np.int64),
                            "max_new_tokens": int(olen[i])})
    return out


def train_tokens(mix, seed, vocab, pool=64):
    """[pool, batch, seq + 1] token ids, every row different: step i
    trains on row-block ``i % pool`` (inputs ``[..., :-1]``, next-token
    labels ``[..., 1:]``)."""
    return _rng(seed, 3).integers(
        0, vocab, (pool, int(mix["batch"]), int(mix["seq"]) + 1),
        dtype=np.int32)
