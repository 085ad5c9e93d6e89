"""The traffic generator is a pure function of (mix, seed) whose lengths
match the mix file; the request arithmetic on a hand-made list."""

import json
import os

import numpy as np
import pytest

import chip_bench_paths as paths
import drive_serve
import loadgen

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(paths.BENCH, "traffic"))
               if f.endswith(".json"))
SERVE_MIXES = [m for m in MIXES if json.load(open(os.path.join(
    paths.BENCH, "traffic", m + ".json")))["kind"] == "serve"]


def mix(name):
    with open(os.path.join(paths.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_schedule_is_a_pure_function_of_the_mix(name):
    a = loadgen.make_requests(mix(name), 40)
    assert a == loadgen.make_requests(mix(name), 40)
    other = loadgen.make_requests(
        dict(mix(name), schedule_seed=mix(name)["schedule_seed"] + 1), 40)
    assert [r["n_prompt"] for r in a][:20] != \
        [r["n_prompt"] for r in other][:20]
    if mix(name)["loop"] == "open":
        assert all(0 <= r["due_s"] < 40 for r in a)
        assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
        assert [r["due_s"] for r in a][:20] != \
            [r["due_s"] for r in other][:20]
        # a shorter window offers the start of the longer one's schedule
        assert loadgen.make_requests(mix(name), 25) == \
            [r for r in a if r["due_s"] < 25]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_every_mix_names_its_schedule_seed(name):
    assert isinstance(mix(name)["schedule_seed"], int)
    with pytest.raises(KeyError):
        loadgen.make_requests(
            {k: v for k, v in mix(name).items() if k != "schedule_seed"}, 10)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_tokens_are_a_pure_function_of_seed_and_index(seed):
    pa = loadgen.prompt_tokens(seed, 3, 50, 32768)
    assert np.array_equal(pa, loadgen.prompt_tokens(seed, 3, 50, 32768))
    assert not np.array_equal(pa, loadgen.prompt_tokens(seed, 4, 50, 32768))
    assert not np.array_equal(pa, loadgen.prompt_tokens(seed + 1, 3, 50,
                                                        32768))
    assert pa.dtype == np.int32 and pa.min() >= 0 and pa.max() < 32768


@pytest.mark.parametrize("name", SERVE_MIXES)
@pytest.mark.parametrize("key", ["prompt_len", "output_len"])
def test_length_quantiles_match_the_file(name, key):
    spec = mix(name)[key]
    x = loadgen.sample_lengths(spec, 20000, np.random.default_rng(3))
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    med = float(np.median(x))
    if spec["dist"] == "lognormal":
        assert abs(med - spec["median"]) <= 0.03 * spec["median"]
        # a quarter of the mass lies above median * exp(0.6745 sigma)
        q75 = spec["median"] * np.exp(0.6745 * spec["sigma"])
        assert abs(np.percentile(x, 75) - min(q75, spec["max"])) \
            <= 0.03 * q75
    elif spec["dist"] == "loguniform":
        assert abs(med - (spec["min"] * spec["max"]) ** 0.5) <= 0.03 * med
    elif spec["dist"] == "uniform":
        assert abs(med - (spec["min"] + spec["max"]) / 2) <= \
            0.03 * (spec["max"] - spec["min"])


@pytest.mark.parametrize("rate", [2.5, 4.0, 10.0])
def test_poisson_arrivals_are_independent_exponential_gaps(rate):
    """What a queue feels: the counts per bin are as dispersed as a
    Poisson process's (variance = mean), on the timescale of a TTFT
    tail, and the gaps' coefficient of variation is 1."""
    seconds = 4000
    due = loadgen.arrival_times({"process": "poisson"}, rate, seconds,
                                np.random.default_rng(5))
    assert abs(len(due) - rate * seconds) <= 4 * (rate * seconds) ** 0.5
    assert 0 <= due[0] and due[-1] < seconds and np.all(np.diff(due) > 0)
    gaps = np.diff(due)
    assert abs(gaps.std() / gaps.mean() - 1) <= 0.05
    for width in (2, 5):
        counts = np.histogram(due, np.arange(0, seconds + 1, width))[0]
        assert abs(counts.var() / counts.mean() - 1) <= 0.15
    # neighbouring gaps are uncorrelated
    assert abs(np.corrcoef(gaps[:-1], gaps[1:])[0, 1]) <= 0.05


def test_the_chat_schedule_is_as_bursty_as_its_process():
    """The committed open-loop schedule itself, at the benchmark's
    window: one draw, so loose limits, but not an evened-out stream
    (dispersion of the counts in 5 s bins was 0.19 for one)."""
    due = np.array([r["due_s"] for r in
                    loadgen.make_requests(mix("chat-steady"), 40)])
    counts = np.histogram(due, np.arange(0, 41, 5))[0]
    assert counts.var() / counts.mean() > 0.4
    gaps = np.diff(due)
    assert 0.8 <= gaps.std() / gaps.mean() <= 1.25


@pytest.mark.parametrize("bad", [{"loop": "burst"},
                                 {"prompt_len": {"dist": "gamma"}},
                                 {"arrivals": {"process": "gamma"}}])
def test_unknown_parameters_are_errors(bad):
    with pytest.raises(ValueError):
        loadgen.make_requests(dict(mix("chat-steady"), **bad), 10)


def record(due, first, last, n_out, n_prompt=100, state="finished",
           finish=None):
    return {"due_s": due, "submit_s": due + 0.01, "t_first_s": first,
            "t_last_s": last, "finish_s": last if finish is None else finish,
            "n_out": n_out, "n_prompt": n_prompt, "state": state}


def test_request_metrics_on_a_hand_made_list():
    window = 10.0
    rows = [record(0.0, 0.5, 1.5, 11),             # ttft .5, tpot .1
            record(1.0, 1.2, 3.2, 21),             # ttft .2, tpot .1
            record(2.0, 2.4, 2.4, 1),              # one token: no tpot
            record(9.0, 9.5, 11.5, 11),            # finishes after window
            record(3.0, 3.1, 3.3, 3, state="failed"),
            record(4.0, None, None, 0, state="waiting", finish=None)]
    rows[-1]["finish_s"] = None
    m = loadgen.request_metrics(rows, window)
    assert m["attempted"] == 6 and m["failed"] == 2
    # finished inside the window: rows 0, 1, 2 -> prompt + output tokens
    assert m["req_tokens_per_s"] == pytest.approx(
        (100 + 11 + 100 + 21 + 100 + 1) / window)
    ttft = [0.5, 0.2, 0.4, 0.5, window, window]
    assert m["ttft_p90_ms"] == pytest.approx(np.percentile(ttft, 90) * 1e3)
    tpot = [0.1, 0.1, 0.2, window, window]
    assert m["tpot_p90_ms"] == pytest.approx(np.percentile(tpot, 90) * 1e3)
    assert m["lateness_mean_s"] == pytest.approx(0.01)
    assert m["lateness_median_s"] == pytest.approx(0.01)
    assert m["lateness_max_s"] == pytest.approx(0.01)


STEP_S, CHAT = 0.12, 588        # falcon-h1.chat: 14.4/s x 40 s, steps of 0.12 s


def late_records(lateness):
    """Finished requests due evenly over 40 s, each submitted
    ``lateness[i]`` seconds after it was due."""
    due = np.linspace(0.0, 40.0, len(lateness), endpoint=False)
    rows = [record(d, d + 1.0, d + 2.0, 11) for d in due]
    for row, late in zip(rows, lateness):
        row["submit_s"] = row["due_s"] + float(late)
    return rows


def half_a_step(n):
    # submitted at the first step boundary after the due time
    return np.random.default_rng(53).uniform(0.0, STEP_S, n)


def one_stall():
    """A sound run in which the host stood still once for 3 s: the 43
    requests due in it (14.4/s x 3 s) are late by what was left of it."""
    late = half_a_step(CHAT)
    late[300:343] = np.linspace(3.0, 0.0, 43, endpoint=False)
    return late


@pytest.mark.parametrize("name,lateness,on_time,old_rule_fails", [
    # every request late by a step and a half: a starved generator
    ("starved", np.full(CHAT, 1.5 * STEP_S), False, 1),
    # a submit() that blocks: the open loop has in effect closed, and
    # each request is later than the one before it
    ("closed_in_effect", np.linspace(0.0, 8.0, CHAT), False, 1),
    ("sound", half_a_step(CHAT), True, 0),
    # one stalled step: the mean passes a step, the median does not
    ("one_stall", one_stall(), True, 1),
    ("two_stalls", np.concatenate([one_stall()[:343], one_stall()[98:]]),
     True, 1),
    # half of the run late: that is no stall
    ("half_late", np.concatenate([half_a_step(290), np.full(298, 2.0)]),
     False, 1),
    # a closed loop submits a request when the one before it finishes
    ("closed_loop", np.zeros(64), True, 0),
])
def test_generator_on_time_reads_the_median(name, lateness, on_time,
                                            old_rule_fails):
    m = loadgen.request_metrics(late_records(lateness), 40.0)
    ok, notes = drive_serve.generator_lateness(m, STEP_S)
    assert ok is on_time, notes
    assert notes["late_runs_mean_over_step"] == old_rule_fails
    assert set(notes) == {"lateness_mean_s", "lateness_median_s",
                          "lateness_max_s", "late_runs_mean_over_step"}
    assert notes["lateness_max_s"] == pytest.approx(float(np.max(lateness)))
    assert notes["lateness_median_s"] == pytest.approx(
        float(np.median(lateness)))
    # the stall stays in the tails, which are timed from the due time
    assert m["failed"] == 0 and m["ttft_p90_ms"] == pytest.approx(1000.0)


def test_a_stall_is_in_the_numbers_the_rule_no_longer_fails():
    m = loadgen.request_metrics(late_records(one_stall()), 40.0)
    ok, notes = drive_serve.generator_lateness(m, STEP_S)
    assert ok and notes["lateness_max_s"] == 3.0
    assert notes["lateness_mean_s"] > 1.3 * STEP_S > STEP_S > \
        notes["lateness_median_s"] > 0.4 * STEP_S
    # just under one mean step the run is on time; just over it, it is not
    for late, want in ((STEP_S * 0.999, True), (STEP_S * 1.001, False)):
        m = loadgen.request_metrics(late_records(np.full(9, late)), 40.0)
        assert drive_serve.generator_lateness(m, STEP_S)[0] is want


def test_request_metrics_with_nothing_finished_is_the_window():
    rows = [record(0.0, None, None, 0, state="shed")]
    m = loadgen.request_metrics(rows, 5.0)
    assert m["ttft_p90_ms"] == 5000.0 and m["tpot_p90_ms"] == 5000.0
    assert m["req_tokens_per_s"] == 0.0 and m["failed"] == 1


def test_zipf_tokens_are_seeded_and_skewed():
    z = loadgen.ZipfTokens(50257, 1.0)
    a = z.batch(np.random.default_rng(loadgen.seed_words(2 ** 31 + 3)), 4, 256)
    b = z.batch(np.random.default_rng(loadgen.seed_words(2 ** 31 + 3)), 4, 256)
    assert np.array_equal(a, b) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 50257
    # rank 1 carries 1/H(50257) ~ 8.8% of the mass, the top ten ~ 26%
    assert 0.15 < np.mean(a < 10) < 0.40
