"""Slot-bound, the decoding slots ride the step's prefill dispatch as
one-token rows (``ServingScheduler._plan_ride`` / ``_ride_rows``): a
ride is one decode step computed by the prefill program, so the tokens
that come out are the no-ride run's, token for token, for a paged GQA
model, a slot-state hybrid and a window-ring model, greedy and seeded
policy alike; a rider moves ``lengths`` and never ``prefill_pos``;
with nothing waiting the dispatch is what it was; slots that are not
plain decode steps never ride; a rider's page grows under the per-slot
containment of a prompt row; a rider whose token ends its request
retires at the boundary.

The ride is forced by step walls seeded into ``_step_cost`` and held
there (``add`` stubbed): the tiny engines' own walls on a CPU would
make the form a matter of the machine."""

import functools

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.llama import Llama, llama_tiny
from deepspeed_tpu.models.mimo_v2 import MiMoV2, mimo_v2_tiny
from deepspeed_tpu.models.nemotron_h import NemotronH, nemotron_h_tiny
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.serving.page_manager import PagePoolExhausted
from deepspeed_tpu.serving.scheduler import RIDE, Request

CFG = dict(num_slots=3, num_pages=24, page_size=8, max_pages_per_slot=8,
           prefill_chunk=8, decode_horizon_steps=8)
MODELS = {
    "paged_gqa": lambda: Llama(llama_tiny()),
    "slot_state_hybrid": lambda: NemotronH(nemotron_h_tiny()),
    "window_ring": lambda: MiMoV2(mimo_v2_tiny()),
}
# (prompt tokens, max_new): more requests than slots, so admission
# leaves some waiting until the last are in
SHAPES = [(40, 6), (33, 9), (12, 5), (25, 12), (20, 7), (36, 10), (14, 4),
          (30, 8), (21, 6)]


@functools.lru_cache(maxsize=None)
def build(name):
    eng = deepspeed_tpu.init_inference(
        MODELS[name](), dtype="float32", kv_cache_dtype="float32")
    eng.init_params(seed=3)
    return eng


@pytest.fixture(scope="module", params=sorted(MODELS))
def engine(request):
    return build(request.param)


@pytest.fixture(scope="module")
def gqa():
    return build("paged_gqa")


# seconds a step of (RIDE, 0) / (RIDE, 1) costs, by the form to force
RIDE_WALLS = {None: (10.0, 10.0), 0: (1e-6, 2e-6), 1: (10.0, 1e-6)}


def hold_walls(sched, ride_after):
    """Step walls of a program whose step costs ``30 + 8.75 h`` ms and
    whose ride forms cost next to nothing, the one followed by a
    horizon of ``ride_after`` least (None: so much that no row ever
    rides), held there."""
    for h in (2, 8):
        for _ in range(5):
            sched._step_cost.add(h, (30.0 + 8.75 * h) / 1e3)
    for after, wall_s in enumerate(RIDE_WALLS[ride_after]):
        for _ in range(3):
            sched._step_cost.add((RIDE, after), wall_s)
    sched._step_cost.add = lambda form, wall_s: None


def submit_all(sched, mixed=True, eos=None, seed=11, shapes=SHAPES):
    """The requests of SHAPES; with ``mixed`` every other one samples
    under a seeded policy with penalties, the rest are greedy."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (n, new) in enumerate(shapes):
        policy = dict(sampling={"do_sample": True, "temperature": 0.9,
                                "top_k": 20, "repetition_penalty": 1.1},
                      seed=100 + i) if mixed and i % 2 else {}
        reqs.append(sched.submit(rng.integers(0, 256, n).astype(np.int32),
                                 max_new_tokens=new, eos_token_id=eos,
                                 **policy))
    return reqs


def serve(engine, ride_after, **kw):
    sched = ServingScheduler(engine, audit_every=1, **CFG)
    hold_walls(sched, ride_after)
    reqs = submit_all(sched, **kw)
    sched.run()
    assert all(r.state == "finished" for r in reqs)
    assert sched.kv.pool.pages_in_use == 0
    return sched, [r.out_tokens for r in reqs]


# ----------------------------------------------------- (a) the same tokens


@pytest.fixture(scope="module")
def no_ride(engine):
    sched, toks = serve(engine, None)
    assert sched.summary()["ride_rows"] == 0
    return toks


@pytest.mark.parametrize("ride_after", [0, 1])
def test_a_ride_emits_the_no_ride_runs_tokens(engine, no_ride, ride_after):
    """Greedy and seeded-policy requests in one batch, riding forced
    with no horizon after it and with a horizon of 1: every stream is
    the no-ride run's; the counters say the rows rode."""
    sched, toks = serve(engine, ride_after)
    assert toks == no_ride
    s = sched.summary()
    assert s["ride_rows"] > 0 and 0 < s["ride_steps_share"] <= 1
    assert (s["horizon_none_share"] > 0) == (ride_after == 0)
    # rows and tokens of a prefill dispatch stay PROMPT rows and tokens
    assert s["prefill_tokens"] == sum(n for n, _ in SHAPES)
    assert s["prefill_rows"] == sum(-(-n // 8) for n, _ in SHAPES)
    # a rider's keys are in what the paged layers' attention handled
    assert s["prefill_kv_tokens"] > sched.metrics.prefill_tokens
    # no program outside the bucket sets
    assert engine.serving_prefill_compile_count() <= \
        len(sched.prefill_row_buckets)
    assert all(h in sched.horizon_buckets for h in sched.metrics.horizons)


# ------------------------------------------- (b) what counts as served


def test_a_rider_moves_lengths_and_never_prefill_pos(gqa):
    """At every step boundary ``prefill_pos <= len(prompt)``; a step's
    riders each gain an emitted token and no prompt token; and once
    both runs have served everything, ``sum(prefill_pos + n_out)`` is
    the no-ride run's."""
    def served(reqs):
        return sum(r.prefill_pos + len(r.out_tokens) for r in reqs)
    totals = {}
    for ride_after in (None, 0):
        sched = ServingScheduler(gqa, **CFG)
        hold_walls(sched, ride_after)
        reqs = submit_all(sched, mixed=False)
        rode = 0
        while True:
            before = {r.rid: (r.prefill_pos, len(r.out_tokens), r.state)
                      for r in reqs}
            busy = sched.step()
            assert all(r.prefill_pos <= len(r.orig_prompt) for r in reqs)
            if sched._riders and not sched._inflight:
                # a step whose decode pass was the dispatch: a request
                # that was running gained its token (and those of a
                # horizon harvested as the step began) and no prompt
                # token
                for r in reqs:
                    pos, out, state = before[r.rid]
                    if state == "running":
                        assert r.prefill_pos == pos == len(r.orig_prompt)
                        assert len(r.out_tokens) > out
                        rode += 1
            if not busy:
                break
        assert (rode > 0) == (ride_after == 0)
        # (a request the step's opening harvest finished is in ``rode``
        # and rode nothing)
        assert 0 <= rode - sched.summary()["ride_rows"] <= len(reqs)
        totals[ride_after] = served(reqs)
    assert totals[0] == totals[None] == \
        sum(n + new for n, new in SHAPES)


# ------------------------------------------------ (c) nothing waiting


def test_nothing_waiting_the_dispatch_is_what_it_was(gqa, monkeypatch):
    """As many requests as slots: admission leaves nobody waiting, no
    row rides whatever the walls say, and every prefill dispatch's
    inputs are the ones it has without the ride forms' walls."""
    seen, calls = [], []
    launch, horizon = gqa.prefill_into_slots, gqa.decode_multi

    def spy(ids, slots, n_valid, *a, **kw):
        seen.append((np.array(ids), np.array(slots), np.array(n_valid)))
        calls.append("prefill")
        return launch(ids, slots, n_valid, *a, **kw)

    def spy_horizon(*a, **kw):
        calls.append("horizon")
        return horizon(*a, **kw)
    monkeypatch.setattr(gqa, "prefill_into_slots", spy)
    monkeypatch.setattr(gqa, "decode_multi", spy_horizon)
    runs = []
    for ride_after in (None, 0):
        del seen[:], calls[:]
        sched = ServingScheduler(gqa, **CFG)
        hold_walls(sched, ride_after)
        reqs = submit_all(sched, mixed=False, shapes=SHAPES[:3])
        plans = []
        while sched.step():
            plans.append((sched._slot_bound, sched._ride, sched._riders))
        assert set(plans) == {(False, None, 0)}
        s = sched.summary()
        assert (s["ride_rows"], s["ride_steps_share"],
                s["horizon_none_share"]) == (0, 0.0, 0.0)
        runs.append((list(seen), [r.out_tokens for r in reqs],
                     list(calls)))
    (a_in, a_out, a_calls), (b_in, b_out, b_calls) = runs
    # the same engine calls in the same order, a horizon after every
    # dispatch: no step's decode pass was its prefill dispatch
    assert a_calls == b_calls and "horizon" in a_calls
    assert a_out == b_out and len(a_in) == len(b_in)
    for x, y in zip(a_in, b_in):
        assert all(np.array_equal(p, q) for p, q in zip(x, y))


# ------------------------------------------------- (d) who never rides


def seat(sched, states):
    """``sched``'s slots filled with requests in ``states`` (9 prompt
    tokens, 40 to emit, 1 emitted where running), unserved."""
    reqs = []
    for slot, state in enumerate(states):
        r = Request(np.zeros(9, np.int32), 40)
        r.state = state
        if state == "running":
            r.out_tokens = [0]
            r.prefill_pos = 9
            sched.lengths[slot] = 9
            assert sched.kv.ensure_capacity(slot, 9)
        reqs.append(r)
    sched.slot_req = reqs
    sched._slot_bound = True
    return reqs


NOT_PLAIN = {
    "grammar": lambda r: setattr(r, "grammar", object()),
    "handoff": lambda r: setattr(r, "handoff", True),
    "sequence_parallel": lambda r: setattr(r, "seq_parallel", True),
}


@pytest.mark.parametrize("kind", sorted(NOT_PLAIN))
def test_a_slot_that_is_no_plain_decode_step_never_rides(gqa, kind):
    """A grammar-constrained, hand-off or sequence-parallel slot is
    not among the riders, and while it runs the plan keeps a horizon
    (a step gives every running slot a token) although the walls say
    no horizon is cheapest."""
    sched = ServingScheduler(gqa, **CFG)
    hold_walls(sched, 0)
    reqs = seat(sched, ["prefill", "running", "running"])
    sched._plan_ride(0.0)
    assert sched._ride == 0
    NOT_PLAIN[kind](reqs[1])
    sched._plan_ride(0.0)
    assert sched._ride == 1
    assert [slot for slot, _, _ in sched._ride_rows()] == [2]
    NOT_PLAIN[kind](reqs[2])
    sched._plan_ride(0.0)
    assert sched._ride is None


def test_no_row_rides_under_a_drafter_or_without_a_prompt_row(gqa):
    sched = ServingScheduler(gqa, spec_decode="ngram", **CFG)
    hold_walls(sched, 0)
    seat(sched, ["prefill", "running", "running"])
    sched._plan_ride(0.0)
    assert sched._ride is None, "speculation keeps its own rounds"
    sched = ServingScheduler(gqa, **CFG)
    hold_walls(sched, 0)
    seat(sched, ["running", "running", "running"])
    sched._plan_ride(0.0)
    assert sched._ride is None, "no prefill dispatch to ride"
    seat(sched, ["prefill", "prefill", "prefill"])
    sched._plan_ride(0.0)
    assert sched._ride is None, "no slot decodes"
    seat(sched, ["prefill", "running", "running"])
    sched._slot_bound = False
    sched._plan_ride(0.0)
    assert sched._ride is None, "nobody waits for a slot"


# ------------------------------------------------- (e) a rider's page


def test_a_rider_crossing_a_page_boundary_grows_its_page(gqa):
    sched = ServingScheduler(gqa, **CFG)
    hold_walls(sched, 0)
    seat(sched, ["prefill", "running", "running"])
    sched.lengths[1] = 8          # its page is full: the token opens one
    sched.kv.truncate_slot(1, 8)
    pages = [len(sched.kv._slot_pages[s]) for s in (1, 2)]
    assert pages == [1, 2]
    rows = sched._ride_rows()
    assert [(s, c) for s, _, c in rows] == [(1, [0]), (2, [0])]
    assert [len(sched.kv._slot_pages[s]) for s in (1, 2)] == [2, 2]


def test_a_rider_whose_growth_fails_is_shed_alone(gqa, no_ride_gqa):
    """The growth of ONE rider raises: that request is shed with the
    reason, the other rows ride on and every other stream is the
    no-ride run's."""
    sched = ServingScheduler(gqa, audit_every=1, **CFG)
    hold_walls(sched, 0)
    reqs = submit_all(sched, mixed=False)
    grow = sched._grow_or_evict
    hit = []

    def failing(slot, target_len):
        req = sched.slot_req[slot]
        if req is reqs[1] and req.state == "running" and not hit \
                and len(req.out_tokens) == 3:
            hit.append(target_len)
            raise PagePoolExhausted("injected: no page for the rider")
        return grow(slot, target_len)
    sched._grow_or_evict = failing
    sched.run()
    assert hit and reqs[1].state == "shed"
    assert "page capacity" in reqs[1].error
    assert reqs[1].out_tokens == no_ride_gqa[1][:3]
    for i, r in enumerate(reqs):
        if i != 1:
            assert r.state == "finished" and r.out_tokens == no_ride_gqa[i]
    assert sched.kv.pool.pages_in_use == 0


@pytest.fixture(scope="module")
def no_ride_gqa(gqa):
    return serve(gqa, None, mixed=False)[1]


# --------------------------------------- (f) retiring at the boundary


def test_a_rider_whose_token_ends_its_request_retires_there(gqa,
                                                            no_ride_gqa):
    """An eos taken off the measured stream, and budgets that run out:
    both end a request on a token the ride computed, in a step that
    launched no horizon; the slot is admitted into in the same step."""
    eos = no_ride_gqa[3][5]
    want = [t[:t.index(eos) + 1] if eos in t else t for t in no_ride_gqa]
    sched = ServingScheduler(gqa, audit_every=1, **CFG)
    hold_walls(sched, 0)
    reqs = submit_all(sched, mixed=False, eos=eos)
    retired_riding = refilled = 0
    while True:
        running = [r for r in sched.slot_req
                   if r is not None and r.state == "running"]
        waiting = len(sched.waiting)
        busy = sched.step()
        if sched._riders and not sched._inflight:
            done = [r for r in running if r.state == "finished"]
            retired_riding += len(done)
            if done and waiting:
                refilled += sched.slot_req.count(None) == 0
        if not busy:
            break
    assert [r.out_tokens for r in reqs] == want
    assert retired_riding > 0 and refilled > 0
    assert sched.summary()["horizon_none_share"] > 0
    assert sched.kv.pool.pages_in_use == 0
