"""Slot-bound, a prefill dispatch whose step launches no horizon stays
in flight across the step boundary: the next step's dispatch is
planned, staged and launched before its sampled tokens are pulled
(``ServingScheduler._launch_boundary`` / ``_advance_in_flight`` /
``_pull``).  The order moves and nothing else: every request's tokens
and final state are the barrier path's, which is the same step with the
pull in front (forced here through the fall-back itself,
``_why_pull_now``, never through an option).  What the pull can still
overturn is contained: an end of sequence, a cancel, a deadline or a
failing callback found at the pull leaves one computed row to drop
(``prefill_overrun_rows``), on pages the slot held until that row's
dispatch was pulled.

The ride is forced as in ``test_serving_ride.py`` (``hold_walls``: step
walls seeded into ``_step_cost`` and held there).

The second half of the file holds the step that launches a decode
horizon (walls held so that riding loses) to the same: slot-bound, its
prefill dispatch goes out before the harvest of the horizon in flight
(``_why_harvest_first`` / ``_why_harvest_now``), and its horizon before
the pull of the dispatch's first tokens, off the device's copy of them
(``_last_tok_on_device``); the reference there is ``overlap=False``."""

import functools
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.llama import Llama, llama_tiny
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.serving.scheduler import RIDE
from tests.unit.test_serving_ride import hold_walls

CFG = dict(num_slots=3, num_pages=24, page_size=8, max_pages_per_slot=8,
           prefill_chunk=8, decode_horizon_steps=8)
# (prompt tokens, max_new): more requests than slots
SHAPES = [(40, 6), (33, 9), (12, 5), (25, 12), (20, 7), (36, 10), (14, 4),
          (30, 8), (21, 6)]


@functools.lru_cache(maxsize=None)
def engine():
    eng = deepspeed_tpu.init_inference(
        Llama(llama_tiny()), dtype="float32", kv_cache_dtype="float32")
    eng.init_params(seed=3)
    return eng


def scheduler(barrier=False, **kw):
    sched = ServingScheduler(engine(), audit_every=1, **dict(CFG, **kw))
    hold_walls(sched, 0)     # every decoding slot rides, no horizon after
    if barrier:
        # the fall-back: every boundary is pulled where it is launched
        sched._why_pull_now = lambda rec: "other"
    return sched


def submit_all(sched, shapes=SHAPES, eos=None, on_token=None, policy=None):
    rng = np.random.default_rng(11)
    reqs = []
    for i, (n, new) in enumerate(shapes):
        extra = policy(i) if policy else {}
        reqs.append(sched.submit(
            rng.integers(0, 256, n).astype(np.int32), max_new_tokens=new,
            eos_token_id=eos, on_token=on_token, **extra))
    return reqs


def serve(barrier, occupancy=None, **kw):
    """Serve SHAPES to the end; returns (scheduler, requests).  At
    every step boundary the pool's books are audited (``audit_every``)
    and a parked slot's pages are still its own."""
    sched = scheduler(barrier, **kw.pop("sched", {}))
    reqs = submit_all(sched, **kw)
    while True:
        waiting = len(sched.waiting)
        busy = sched.step()
        for slot in sched._zombies:
            assert sched.slot_req[slot] is None
            assert sched.kv.slot_page_count(slot) > 0
        if occupancy is not None and waiting and sched.waiting:
            occupancy.append(sched.slot_req.count(None))
        if not busy:
            break
    assert not sched._pf_flight and not sched._zombies
    assert sched.kv.pool.pages_in_use == 0
    assert all(r.owed == 0 for r in reqs)
    return sched, reqs


def outcome(reqs):
    return [(r.state, r.out_tokens) for r in reqs]


@functools.lru_cache(maxsize=None)
def plain_tokens():
    """The barrier path's tokens with no eos and nothing else."""
    return tuple(tuple(r.out_tokens) for r in serve(True)[1])


def eos_of_the_stream():
    """A token several requests emit mid-stream, so an end of sequence
    lands while they ride."""
    return plain_tokens()[3][5]


def cancel_at(n_prompt, n_tokens):
    """Cancels the request of ``n_prompt`` prompt tokens from its own
    callback, at its ``n_tokens``-th token."""
    def on_token(req, tok):
        if len(req.orig_prompt) == n_prompt and \
                len(req.out_tokens) == n_tokens:
            req.cancel()
    return on_token


def expire_at(n_prompt, n_tokens):
    def on_token(req, tok):
        if len(req.orig_prompt) == n_prompt and \
                len(req.out_tokens) == n_tokens:
            req.deadline = time.monotonic() - 1.0
    return on_token


def sampled_penalised(i):
    return dict(sampling={"do_sample": True, "temperature": 0.9,
                          "top_k": 20, "repetition_penalty": 1.1},
                seed=100 + i) if i % 2 else {}


def with_grammar(i):
    return {"grammar": {"regex": "[ab]{2,6}"}} if i == 4 else {}


# name -> (kwargs of serve(), what the look-ahead run's summary must say)
CASES = {
    "no_eos": (lambda: {}, lambda s: (
        s["prefill_lookahead_share"] > 0.5
        and s["prefill_overrun_rows"] == 0)),
    "eos_mid_ride": (lambda: {"eos": eos_of_the_stream()}, lambda s: (
        s["prefill_lookahead_share"] > 0.3
        and s["prefill_overrun_rows"] > 0)),
    "cancel_found_late": (
        lambda: {"on_token": cancel_at(SHAPES[3][0], 4)}, lambda s: (
            s["cancelled"] == 1 and s["prefill_overrun_rows"] >= 1)),
    "deadline_found_late": (
        lambda: {"on_token": expire_at(SHAPES[4][0], 3)}, lambda s: (
            s["shed"] == 1 and s["prefill_overrun_rows"] >= 1)),
    "page_pressure_evicts": (
        lambda: {"sched": {"num_pages": 11}}, lambda s: (
            s["preemptions"] > 0
            and s["prefill_lookahead_fallbacks"].get("eviction", 0) > 0)),
    "penalty_rows": (lambda: {"policy": sampled_penalised}, lambda s: (
        s["prefill_lookahead_fallbacks"].get("policy", 0) > 0)),
    "grammar_row": (lambda: {"policy": with_grammar}, lambda s: (
        s["grammar_requests"] == 1
        and s["prefill_lookahead_fallbacks"].get("policy", 0) > 0)),
    "horizon_every_step": (
        lambda: {"shapes": SHAPES[:3]}, lambda s: (
            s["prefill_lookahead_share"] == 0.0
            and s["horizon_lookahead_share"] == 1.0
            and set(s["prefill_lookahead_fallbacks"]) == {"not_slot_bound"})),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tokens_and_states_are_the_barrier_paths(case):
    make, says = CASES[case]
    _, want = serve(True, **make())
    sched, got = serve(False, **make())
    assert outcome(got) == outcome(want)
    s = sched.summary()
    assert says(s), s


def test_ids_built_on_the_device_keep_the_prefill_programs_signature():
    """A fresh engine serves both ways: ids built on the device are
    staged as the host's are, so the prefill program keeps its one
    signature a row bucket, and the two programs that keep a sample on
    the device are built once a bucket."""
    engine.cache_clear()
    try:
        serve(True)
        sched, _ = serve(False)
        eng = engine()
        assert sched.summary()["prefill_lookahead_share"] > 0.5
        assert eng.serving_prefill_compile_count() <= \
            len(sched.prefill_row_buckets)
        keep, ids, merge = eng._token_feedback_fns()
        assert 1 <= keep._cache_size() <= len(sched.prefill_row_buckets)
        assert 1 <= ids._cache_size() <= len(sched.prefill_row_buckets)
        assert merge._cache_size() == 1
    finally:
        engine.cache_clear()
        plain_tokens.cache_clear()


def test_the_barrier_path_never_looks_ahead():
    sched, _ = serve(True)
    s = sched.summary()
    assert s["prefill_lookahead_share"] == 0.0
    assert s["prefill_overrun_rows"] == 0
    assert s["prefill_lookahead_fallbacks"]["other"] > 0


def test_an_eos_request_engages_and_its_overrun_row_is_contained():
    """A request that carries an eos rides ahead like any other; where
    its token WAS the eos, the row the next dispatch computed for it is
    dropped, its slot stays parked (pages held, nobody admitted) until
    that dispatch's pull, and the pages go back right after."""
    eos = eos_of_the_stream()
    sched = scheduler()
    reqs = submit_all(sched, eos=eos)
    parked_steps = 0
    while True:
        parked = {s: sched.kv.slot_page_count(s) for s in sched._zombies}
        flight = len(sched._pf_flight)
        busy = sched.step()
        for slot, pages in parked.items():
            # parked across ONE boundary: the dispatch that held the
            # overrun row was in flight, and is pulled in this step
            assert flight == 1 and pages > 0
            assert slot not in sched._zombies
            parked_steps += 1
        if not busy:
            break
    want = [list(t[:t.index(eos) + 1]) if eos in t else list(t)
            for t in plain_tokens()]
    assert [r.out_tokens for r in reqs] == want
    assert all(r.state == "finished" for r in reqs)
    s = sched.summary()
    assert parked_steps > 0 and s["prefill_overrun_rows"] == parked_steps
    assert s["prefill_lookahead_share"] > 0.3
    assert sched.kv.pool.pages_in_use == 0


def test_a_slot_freed_by_length_is_admitted_into_in_the_same_plan():
    """A rider whose owed token is its last leaves its slot before the
    next step's admission: while requests wait, no slot stands empty at
    any step boundary."""
    empty = []
    sched, reqs = serve(False, occupancy=empty)
    assert empty and set(empty) == {0}
    assert sched.summary()["prefill_lookahead_share"] > 0.5
    assert [tuple(r.out_tokens) for r in reqs] == list(plain_tokens())


def test_on_token_order_within_a_request_and_out_tokens_match():
    """Every request's callbacks fire once a token, in the order of its
    stream, and ``out_tokens`` is what they saw, both ways."""
    seen = {}

    def on_token(req, tok):
        assert req.out_tokens[-1] == tok
        seen.setdefault(req.rid, []).append((len(req.out_tokens), tok))
    streams = []
    for barrier in (True, False):
        seen.clear()
        _, reqs = serve(barrier, on_token=on_token)
        for r in reqs:
            assert [n for n, _ in seen[r.rid]] == \
                list(range(1, len(r.out_tokens) + 1))
            assert [t for _, t in seen[r.rid]] == r.out_tokens
        streams.append([r.out_tokens for r in reqs])
    assert streams[0] == streams[1] == [list(t) for t in plain_tokens()]


def test_a_cancel_and_a_failing_callback_found_at_the_pull():
    """A cancel that lands while a request's token is still on the
    device, and a callback that raises on a token pulled late: each is
    closed at the pull that finds it (cancelled, failed), its tokens a
    prefix of the plain run's, every other stream whole."""
    sched = scheduler()

    def on_token(req, tok):
        if req is reqs[4] and len(req.out_tokens) == 3:
            reqs[3].cancel()          # found at reqs[3]'s next pull
        if req is reqs[5] and len(req.out_tokens) == 2:
            raise RuntimeError("client went away")
    reqs = submit_all(sched, on_token=on_token)
    sched.run()
    plain = [list(t) for t in plain_tokens()]
    assert reqs[3].state == "cancelled"
    assert 0 < len(reqs[3].out_tokens) < len(plain[3])
    assert reqs[5].state == "failed" and "client went away" in reqs[5].error
    for i, r in enumerate(reqs):
        assert r.out_tokens == plain[i][:len(r.out_tokens)]
        if i not in (3, 5):
            assert r.state == "finished" and r.out_tokens == plain[i]
    assert len(reqs[5].out_tokens) == 2
    assert not sched._pf_flight and not sched._zombies
    assert sched.kv.pool.pages_in_use == 0
    sched.audit()


def test_a_cycle_under_look_ahead_is_filed_pull_to_pull():
    """``(RIDE, 0)``'s walls stay one dispatch's time: a dispatch that
    was in flight across a boundary ends its cycle at its pull, and the
    next cycle starts there."""
    sched = scheduler()
    filed = []
    sched._step_cost.add = lambda form, wall_s: filed.append((form, wall_s))
    submit_all(sched)
    t0 = time.monotonic()
    sched.run()
    wall = time.monotonic() - t0
    rides = [w for f, w in filed if f == (RIDE, 0)]
    s = sched.summary()
    assert len(rides) >= s["prefill_lookahead_share"] * \
        s["prefill_dispatches"]
    assert all(w > 0 for w in rides) and sum(rides) <= wall


def test_drain_pulls_what_is_in_flight():
    sched = scheduler()
    reqs = submit_all(sched)
    while not sched._pf_flight:
        sched.step()
    counts = sched.drain(grace_s=None, shed_waiting=True)
    assert not sched._pf_flight and not sched._zombies
    assert all(r.owed == 0 for r in reqs)
    assert counts["finished"] + counts["shed"] > 0
    assert sched.summary()["prefill_lookahead_fallbacks"].get("drain", 0) > 0
    assert sched.kv.pool.pages_in_use == 0


# ------------------------------------------- the step that launches a horizon
def horizon_scheduler(overlap, **kw):
    sched = ServingScheduler(engine(), audit_every=1, overlap=overlap,
                             **dict(CFG, **kw))
    hold_walls(sched, None)      # riding loses: a horizon every step
    evict = sched._preempt_youngest

    def preempt(*a, **k):
        # no page is taken from a slot while a horizon is in flight
        assert not sched._inflight
        return evict(*a, **k)
    sched._preempt_youngest = preempt
    return sched


def serve_horizons(overlap, arrive=None, **kw):
    """Serve to the end with a horizon every step; returns (scheduler,
    requests).  ``arrive(sched, reqs)`` is called between steps."""
    sched = horizon_scheduler(overlap, **kw.pop("sched", {}))
    reqs = submit_all(sched, **kw)
    while sched.step():
        for slot in sched._zombies:
            assert sched.slot_req[slot] is None
            assert sched.kv.slot_page_count(slot) > 0
        assert not sched._pf_flight     # pulled in the step that launched it
        if arrive is not None:
            arrive(sched, reqs)
    assert not sched._inflight and not sched._zombies
    cache = sched.prefix_cache
    assert sched.kv.pool.pages_in_use == (
        0 if cache is None else cache.cached_pages)
    assert all(r.owed == 0 for r in reqs)
    sched.audit()
    return sched, reqs


@functools.lru_cache(maxsize=None)
def horizon_tokens():
    return tuple(tuple(r.out_tokens) for r in serve_horizons(False)[1])


def a_first_token():
    """The first token of one request that another emits later on: as
    the end of sequence it ends the one at the pull its horizon was
    launched ahead of, and the other in the middle of a horizon."""
    streams = horizon_tokens()
    return next(t[0] for t in streams
                if any(t[0] in u[1:] for u in streams if u is not t))


def a_later_token():
    """A token some request emits past its first and none emits first."""
    firsts = {t[0] for t in horizon_tokens()}
    return next(tok for t in horizon_tokens() for tok in t[2:]
                if tok not in firsts)


def ended_at_first(eos):
    return sum(t[0] == eos for t in horizon_tokens())


SAMPLED = dict(do_sample=True, temperature=0.9, top_k=20)
ONE_TOKEN = [(n, 1 if i % 3 == 0 else new)
             for i, (n, new) in enumerate(SHAPES)]

# name -> (kwargs of serve_horizons(), what the look-ahead run's summary
# must say)
HORIZON_CASES = {
    "slot_bound_greedy": (lambda: {}, lambda s: (
        s["prefill_lookahead_share"] > 0.5
        and s["horizon_lookahead_share"] == 1.0
        and s["prefill_overrun_rows"] == 0
        and s["ride_steps_share"] == 0.0)),
    "slot_bound_sampled_default": (lambda: {"sched": SAMPLED}, lambda s: (
        s["prefill_lookahead_share"] == 0.0
        and s["horizon_lookahead_share"] == 0.0
        and s["prefill_lookahead_fallbacks"].get("policy", 0) > 0
        and set(s["prefill_lookahead_fallbacks"]) <= {
            "policy", "not_slot_bound"})),
    "slot_bound_sampled_rows": (
        lambda: {"policy": sampled_penalised}, lambda s: (
            s["prefill_lookahead_fallbacks"].get("policy", 0) > 0)),
    "first_token_is_eos": (lambda: {"eos": a_first_token()}, lambda s: (
        ended_at_first(a_first_token()) >= 1
        and s["prefill_overrun_rows"] == ended_at_first(a_first_token())
        and s["horizon_lookahead_share"] == 1.0)),
    "eos_mid_horizon": (lambda: {"eos": a_later_token()}, lambda s: (
        s["prefill_overrun_rows"] == 0
        and s["prefill_lookahead_share"] > 0.3)),
    "one_new_token": (lambda: {"shapes": ONE_TOKEN}, lambda s: (
        s["prefill_overrun_rows"] == 0 and s["completed"] == len(SHAPES))),
    "cancels_at_its_first_token": (
        lambda: {"on_token": cancel_at(SHAPES[3][0], 1)}, lambda s: (
            s["cancelled"] == 1 and s["prefill_overrun_rows"] == 1)),
    "deadline_at_its_first_token": (
        lambda: {"on_token": expire_at(SHAPES[4][0], 1)}, lambda s: (
            s["shed"] == 1 and s["prefill_overrun_rows"] == 1)),
    "page_pressure": (lambda: {"sched": {"num_pages": 11}}, lambda s: (
        s["preemptions"] > 0
        and s["prefill_lookahead_fallbacks"].get("pages", 0) > 0
        and s["prefill_lookahead_share"] > 0)),
    "grammar_row": (lambda: {"policy": with_grammar}, lambda s: (
        s["grammar_requests"] == 1
        and s["prefill_lookahead_fallbacks"].get("policy", 0) > 0
        and 0 < s["prefill_lookahead_share"])),
    # a request that leaves its slot ahead of the harvest donates the
    # pages its tokens on the host vouch for, an overrun slot at the harvest
    "prefix_cache_donations": (
        lambda: {"sched": {"prefix_cache": True, "num_pages": 40},
                 "eos": a_first_token()}, lambda s: (
            s["prefill_lookahead_share"] > 0.3
            and s["prefill_overrun_rows"] >= 1)),
    "open_loop_with_free_slots": (lambda: {"shapes": SHAPES[:3]}, lambda s: (
        s["prefill_lookahead_share"] == 0.0
        and s["horizon_lookahead_share"] == 1.0
        and set(s["prefill_lookahead_fallbacks"]) == {"not_slot_bound"})),
}


@pytest.mark.parametrize("case", sorted(HORIZON_CASES))
def test_horizon_steps_tokens_and_states_are_the_barrier_paths(case):
    make, says = HORIZON_CASES[case]
    barrier, want = serve_horizons(False, **make())
    sched, got = serve_horizons(True, **make())
    assert outcome(got) == outcome(want)
    s = sched.summary()
    assert says(s), s
    b = barrier.summary()
    assert b["prefill_lookahead_share"] == b["horizon_lookahead_share"] == 0
    assert b["prefill_overrun_rows"] == 0
    assert set(b["prefill_lookahead_fallbacks"]) <= {"other", "eviction"}
    # the rule's picks are the barrier run's: what it compares is whole
    # device cycles, whichever order the host works in
    assert s["ride_steps_share"] == b["ride_steps_share"] == 0.0


@pytest.mark.parametrize("what", ["cancel", "deadline", "callback"])
def test_found_at_the_pull_a_horizon_was_launched_ahead_of(what):
    """While a request's first token is still on the device (``owed``)
    another request's callback cancels it, expires it, or its own
    callback raises on that token: the pull closes it, the row its
    horizon computed is dropped at the harvest, its slot parked till
    then, and every other stream is whole."""
    hit = []

    def on_token(req, tok):
        victim = reqs[5]
        if req is victim and what == "callback":
            raise RuntimeError("client went away")
        if victim.owed and not hit and what != "callback":
            hit.append(req)
            if what == "cancel":
                victim.cancel()
            else:
                victim.deadline = time.monotonic() - 1.0
    sched = horizon_scheduler(True)
    reqs = submit_all(sched, on_token=on_token)
    parked = 0
    while sched.step():
        parked += len(sched._zombies)
    want = {"cancel": "cancelled", "deadline": "shed",
            "callback": "failed"}[what]
    assert reqs[5].state == want and reqs[5].out_tokens == \
        list(horizon_tokens()[5][:what == "callback"])
    assert (what == "callback") != bool(hit)
    for i, r in enumerate(reqs):
        if i != 5:
            assert r.state == "finished"
            assert r.out_tokens == list(horizon_tokens()[i])
    s = sched.summary()
    # the token dropped at the pull (a failed request's was delivered)
    # and the row of the horizon dropped at its harvest
    assert s["prefill_overrun_rows"] == 1 + (what != "callback")
    assert parked >= 1
    assert not sched._zombies and sched.kv.pool.pages_in_use == 0
    sched.audit()


def test_an_arrival_with_slots_free_has_its_first_chunk_where_it_had():
    """Nobody waits for a slot, so an arrival during a horizon (here:
    submitted from a callback the harvest runs) is admitted and has its
    first chunk in that step's dispatch, as in the barrier order: the
    dispatch is not launched ahead of the harvest."""
    waited = {}
    for overlap in (False, True):
        sched = horizon_scheduler(overlap, num_slots=4)
        late = []

        def on_token(req, tok):
            if req is reqs[1] and len(req.out_tokens) == 3:
                late.append((sched.step_idx, sched.submit(
                    np.arange(20, dtype=np.int32), max_new_tokens=4)))
        reqs = submit_all(sched, shapes=SHAPES[:3], on_token=on_token)
        while sched.step():
            if late and late[0][1].prefill_pos and overlap not in waited:
                waited[overlap] = sched.step_idx - late[0][0]
        assert late[0][1].state == "finished"
        s = sched.summary()
        assert s["prefill_lookahead_share"] == 0.0
    # submitted in a harvest (the barrier's at the end of a step, the
    # overlapped one's at the start of the next), prefilled in the first
    # step whose admission follows
    assert waited == {False: 1, True: 0}
    assert s["horizon_lookahead_share"] == 1.0


def test_a_horizon_steps_cycle_is_harvest_to_harvest():
    """``_step_cost`` files a horizon step's cycle from the end of the
    last harvest to the end of its own, whatever was launched ahead of
    either: one whole device cycle, not the time the host blocked."""
    sched = ServingScheduler(engine(), audit_every=1, **CFG)
    for h in (2, 8):
        for _ in range(5):
            sched._step_cost.add(h, (30.0 + 8.75 * h) / 1e3)
    for after in (0, 1):
        for _ in range(3):
            sched._step_cost.add((RIDE, after), 10.0)
    starts, ends = [], []
    sched._step_cost.add = lambda form, wall_s: None
    harvest = sched._harvest

    def timed():
        starts.append(sched._inflight[0]["cycle_t0"])
        out = harvest()
        ends.append(sched._cycle_t0)
        return out
    sched._harvest = timed
    step = sched.step

    def stepped():
        idle = not sched._inflight
        busy = step()
        if idle:
            ends.append(sched._cycle_t0)   # the step's own start
        return busy
    sched.step = stepped
    submit_all(sched)
    sched.run()
    assert sched.summary()["prefill_lookahead_share"] > 0.5
    # a cycle began where a harvest ended, or with a step that found
    # the device idle
    began = [t for t in starts if t is not None]
    assert len(began) > 5 and set(began) <= set(ends)
    assert len(set(began) & set(ends[1:])) > 5
