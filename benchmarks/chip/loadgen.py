"""Seeded traffic and the arithmetic over request records.

Pure numpy, no JAX: a traffic mix is a data file of parameters
(``traffic/<mix>.json``) and this one generator reads all of them.

A mix's schedule (arrival times and the pairs of lengths) is ONE
independent draw from the file's distributions under the file's own
``schedule_seed``: exponential gaps for ``poisson`` arrivals, sampled
lengths.  The run's ``--seed`` draws the token ids (and, in the drivers,
the weights), so runs with different seeds carry the same schedule with
other content: measured on the chip (PR 25), seeds that also redrew the
schedule put 3-5% between runs whose same-seed repeats agreed to 1%,
which is the sample's spread and not the system's.
"""

import math

import numpy as np


def seed_words(seed):
    """A driver seed (any whole number to a little over 2**31) as the
    entropy list numpy's SeedSequence takes."""
    seed = int(seed)
    return [seed & 0xFFFFFFFF, seed >> 32]


def sample_lengths(spec, n, rng):
    """``n`` independent integer lengths from ``spec`` = {"dist":
    lognormal|loguniform|uniform|fixed, ...}, clipped to [min, max]
    where given."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif dist == "loguniform":
        x = np.exp(rng.uniform(math.log(spec["min"]),
                               math.log(spec["max"]), n))
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    elif dist == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    x = np.rint(x)
    if "min" in spec:
        x = np.maximum(x, spec["min"])
    if "max" in spec:
        x = np.minimum(x, spec["max"])
    return x.astype(np.int64)


def arrival_times(spec, rate_per_s, seconds, rng):
    """Due times inside [0, seconds) of an arrival process at
    ``rate_per_s``: ``poisson`` = independent exponential gaps, so the
    number of requests is the draw's and only its mean is rate x
    seconds.  A shorter window's schedule is the start of a longer
    one's."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    due = np.zeros(0)
    while not len(due) or due[-1] < seconds:
        gaps = rng.exponential(1.0 / rate_per_s,
                               64 + int(2 * rate_per_s * seconds))
        due = np.concatenate([due, (due[-1] if len(due) else 0.0)
                              + np.cumsum(gaps)])
    return due[due < seconds]


def prompt_tokens(seed, index, length, vocab_size):
    """Request ``index``'s prompt: fresh token ids from (seed, index), so
    no two requests of a run share a prefix."""
    rng = np.random.default_rng(seed_words(seed) + [int(index)])
    return rng.integers(0, vocab_size, int(length), dtype=np.int32)


def make_requests(mix, seconds):
    """The cell's schedule, as dicts with ``due_s`` (open loop; 0.0 in a
    closed loop), ``n_prompt`` and ``max_new``: a function of the mix
    file alone, arrivals and the two lengths each from a stream of its
    own under the file's ``schedule_seed``.  The prompts' tokens come
    from :func:`prompt_tokens` and the run's seed.  Open loop: every
    arrival inside the window.  Closed loop: ``pool`` pairs of lengths,
    which the driver takes in order and cycles as clients free up."""
    arr, pro, out = (np.random.default_rng(s) for s in np.random.SeedSequence(
        seed_words(mix["schedule_seed"])).spawn(3))
    loop = mix["loop"]
    if loop == "open":
        due = arrival_times(mix["arrivals"], mix["rate_per_s"], seconds, arr)
        n = len(due)
    elif loop == "closed":
        n = int(mix["pool"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    prompts = sample_lengths(mix["prompt_len"], n, pro)
    outs = sample_lengths(mix["output_len"], n, out)
    return [{"due_s": float(due[i]), "n_prompt": int(prompts[i]),
             "max_new": int(outs[i])} for i in range(n)]


def percentile(values, q):
    """Linear-interpolated percentile (numpy's default), of a list."""
    return float(np.percentile(np.asarray(values, float), q))


def request_metrics(records, window_s):
    """End-to-end serving metrics over one window's request records.

    A record has ``due_s`` (when the request was due, on the window's
    clock), ``submit_s``, ``t_first_s``/``t_last_s`` (clock times of the
    first and last token, None if none came), ``finish_s``, ``n_out``,
    ``n_prompt`` and ``state``.  The tails are over EVERY request due in
    the window: one that failed, was shed or has no first token by the
    end of the drain counts as the window's length; a request with
    fewer than two tokens has no time per output token and counts the
    same way if it did not finish.  ``req_tokens_per_s`` counts prompt
    and output tokens of requests that finished INSIDE the window."""
    ttft, tpot = [], []
    tokens_done, failed = 0, 0
    for r in records:
        ok = r["state"] == "finished"
        failed += not ok
        if ok and r["t_first_s"] is not None:
            ttft.append(r["t_first_s"] - r["due_s"])
        else:
            ttft.append(window_s)
        if ok and r["n_out"] >= 2:
            tpot.append((r["t_last_s"] - r["t_first_s"]) / (r["n_out"] - 1))
        elif not ok:
            tpot.append(window_s)
        if ok and r["finish_s"] is not None and r["finish_s"] <= window_s:
            tokens_done += r["n_prompt"] + r["n_out"]
    late = [r["submit_s"] - r["due_s"] for r in records
            if r["submit_s"] is not None]
    out = {
        "attempted": len(records), "failed": int(failed),
        "req_tokens_per_s": tokens_done / window_s,
        "lateness_mean_s": float(np.mean(late)) if late else 0.0,
        "lateness_median_s": float(np.median(late)) if late else 0.0,
        "lateness_max_s": float(np.max(late)) if late else 0.0,
    }
    if ttft:
        out["ttft_p90_ms"] = percentile(ttft, 90) * 1e3
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
    if tpot:
        out["tpot_p90_ms"] = percentile(tpot, 90) * 1e3
        out["tpot_p50_ms"] = percentile(tpot, 50) * 1e3
    return out


class ZipfTokens:
    """Token ids with rank r drawn at weight r**-exponent over the whole
    vocabulary (bounded Zipf by inverse CDF): a unigram distribution a
    model can learn, so the loss falls from ln(vocab) inside a window."""

    def __init__(self, vocab_size, exponent):
        w = np.arange(1, vocab_size + 1, dtype=np.float64) ** -exponent
        self.cdf = np.cumsum(w / w.sum())
        self.vocab_size = vocab_size

    def batch(self, rng, batch, seq):
        ids = np.searchsorted(self.cdf, rng.random((batch, seq)))
        return np.minimum(ids, self.vocab_size - 1).astype(np.int32)
