"""``repro.obs``: self time of nested spans, counters per round, the
bounded history, compiles by span, the spans of a chunked round, and the
clock never reaching a round's results."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.params_codec import flatten_params
from repro.data import partition_iid, synthetic_mnist
from repro.fl import FLClient, FLServer, FLSimulation, OrchestrationConfig
from repro.models import lenet5
from repro.train.optim import SGDConfig

PROGRAM_SPANS = (obs.ROUND, obs.REPORT, obs.FINALIZE, obs.SERVER_ENCODE,
                 obs.SCHED_DOWNLINK, obs.SCHED_UPLINK, obs.ASSEMBLE,
                 obs.CLIENT_TRAIN, obs.CLIENT_ENCODE, obs.SERVER_FOLD,
                 obs.WAIT)
BENCHMARK_SPANS = ("fl.round", "fl.disseminate", "fl.train", "fl.encode",
                   "fl.fold")


@pytest.fixture(autouse=True)
def fresh_records():
    obs.reset()
    yield
    obs.reset()


def ticks(times):
    """A clock that reads ``times`` in order."""
    it = iter(times)
    return lambda: next(it)


def q8_sim(clients: int = 4, rows: int = 80, seed: int = 0,
           faults=None) -> FLSimulation:
    """A chunked q8 residual federation: downlink and interleaved uplinks
    on one shared medium."""
    flat, spec = flatten_params(lenet5.init_params(jax.random.PRNGKey(seed)))
    shards = partition_iid(synthetic_mnist(clients * rows, seed=seed),
                           clients, seed=seed)
    fl_clients = [FLClient(client_id=i, data=shards[i],
                           loss_fn=lenet5.loss_fn, spec=spec,
                           sgd=SGDConfig(lr=0.05), seed=seed)
                  for i in range(clients)]
    server = FLServer(OrchestrationConfig(
        num_clients=clients, clients_per_round=clients, min_fraction=0.5,
        num_rounds=3, min_local_samples=32, seed=seed), flat)
    return FLSimulation(server, fl_clients, seed=seed, chunk_elems=4096,
                        chunk_encoding="q8-block", residual_uplink=True,
                        uplink_mode="interleaved", downlink_mode="medium",
                        faults=faults)


def test_program_span_names_are_not_the_benchmarks():
    assert all(n.startswith("fl.") for n in PROGRAM_SPANS)
    assert not set(PROGRAM_SPANS) & set(BENCHMARK_SPANS)


def test_self_time_of_nested_spans_by_hand(monkeypatch):
    # round [0, 20): a [1, 7) holds b [2, 3) and b [4, 6);
    # c [8, 15) holds wait [9, 14)
    monkeypatch.setattr(obs, "clock",
                        ticks([0, 1, 2, 3, 4, 6, 7, 8, 9, 14, 15, 20]))
    with obs.span(obs.ROUND):
        with obs.span("fl.a"):
            with obs.span("fl.b"):
                pass
            with obs.span("fl.b"):
                pass
        with obs.span("fl.c"):
            with obs.span(obs.WAIT):
                pass
    (rec,) = obs.history()
    got = {n: (e["self_s"], e["total_s"], e["calls"])
           for n, e in rec["spans"].items()}
    assert got == {"fl.b": (3, 3, 2), "fl.a": (3, 6, 1),
                   obs.WAIT: (5, 5, 1), "fl.c": (2, 7, 1),
                   obs.ROUND: (7, 20, 1)}
    assert (rec["t0"], rec["t1"]) == (0, 20)
    assert sum(e["self_s"] for e in rec["spans"].values()) == 20


def test_span_returns_nothing_to_its_caller():
    with obs.span("fl.a") as inside:
        assert inside is None


def test_counters_roll_into_the_open_round_and_history_is_bounded():
    obs.count("x", 2)                        # outside every round
    obs.h2d([np.zeros(3, np.float32)])
    n = obs.HISTORY + 5
    for i in range(n):
        with obs.span(obs.ROUND):
            obs.count("x", i)
            with obs.span("fl.a"):
                obs.count("x", 1)            # the innermost open round
            obs.h2d([np.zeros(3, np.float32), jnp.zeros(2)])
            obs.d2h([np.zeros(3), jnp.zeros(2, jnp.float32)])
    hist = obs.history()
    assert len(hist) == obs.HISTORY
    assert [r["counters"]["x"] for r in hist] == [i + 1 for i in range(5, n)]
    assert hist[-1]["counters"] == {
        "x": n, "h2d_bytes": 12, "h2d_transfers": 1,
        "d2h_bytes": 8, "d2h_transfers": 1}
    assert obs.process()["counters"] == {"x": 2, "h2d_bytes": 12,
                                         "h2d_transfers": 1}
    assert obs.process()["spans"] == {}


def test_window_sums_the_rounds_that_started_in_it(monkeypatch):
    monkeypatch.setattr(obs, "clock", ticks(range(100)))
    for i in range(4):                      # rounds start at 0, 4, 8, 12
        with obs.span(obs.ROUND):
            obs.count("x", 10 ** i)
            with obs.span(obs.ASSEMBLE):
                pass
    w = obs.window(4, 12)
    assert w["rounds"] == 2 and w["counters"] == {"x": 110}
    assert w["spans"][obs.ASSEMBLE]["calls"] == 2
    assert w["spans"][obs.ROUND]["total_s"] == 6
    assert obs.window(13, 20) == {"rounds": 0, "spans": {}, "counters": {}}


def test_a_compile_is_counted_under_its_span():
    step = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.arange(7, dtype=np.float32)
    with obs.span(obs.ROUND):
        with obs.span("fl.test.step"):
            step(x).block_until_ready()
        step(x).block_until_ready()          # compiled already
    spans = obs.history()[-1]["spans"]
    assert spans["fl.test.step"]["compiles"] >= 1
    assert spans["fl.test.step"]["compile_s"] > 0
    assert spans[obs.ROUND]["compiles"] == 0
    assert obs.compile_totals()["compiles"] >= spans["fl.test.step"][
        "compiles"]


def test_a_chunked_q8_round_records_every_program_span():
    sim = q8_sim()
    result = sim.run_round()
    assert result.quorum_met and len(result.reporters) == 4
    (rec,) = obs.history()
    spans = rec["spans"]
    assert set(PROGRAM_SPANS) <= set(spans)
    assert spans[obs.ROUND]["calls"] == 1
    assert spans[obs.CLIENT_TRAIN]["calls"] == 4
    assert spans[obs.SERVER_FOLD]["calls"] == 4
    # every span's self time, summed, is the round
    total = spans[obs.ROUND]["total_s"]
    assert sum(e["self_s"] for e in spans.values()) == pytest.approx(
        total, rel=1e-9)
    assert total == pytest.approx(rec["t1"] - rec["t0"], rel=1e-9)
    c = rec["counters"]
    assert c["train_steps"] == 4 * 2         # 64 training rows, batch 32
    assert c["frames_sent"] > 0 and c["frames_lost"] == 0
    # per client: the global's 10 leaves, images and labels of 2 steps
    # and of 2 loss evaluations in; 2 losses and 10 trained leaves out
    assert c["h2d_transfers"] == 4 * (10 + 2 * 2 + 2 * 2)
    assert c["d2h_transfers"] == 4 * (2 + 10)
    assert c["d2h_bytes"] == 4 * (2 * 4 + 4 * lenet5.PARAM_COUNT)


@pytest.mark.parametrize("damage", [False, True],
                         ids=["clean", "frame-faults"])
def test_frames_are_built_only_when_damaged(monkeypatch, damage):
    """``frames_built`` sits beside ``frames_sent``: 0 on a clean medium
    round, one a damaged frame under a ``FaultPlan``."""
    from repro.fl.faults import FaultPlan, FrameFault
    from repro.transport import medium as medium_mod

    damaged = []
    real = medium_mod._damage_frame

    def damage_frame(frame, kind):
        out = real(frame, kind)
        if out is not None:
            damaged.append(frame)
        return out

    monkeypatch.setattr(medium_mod, "_damage_frame", damage_frame)
    plan = FaultPlan(frame_faults=[
        FrameFault("corrupt", client=1, window=0, chunk_index=0)])
    sim = q8_sim(clients=3, rows=48, faults=plan if damage else None)
    sim.run_round()
    (rec,) = obs.history()
    c = rec["counters"]
    assert c["frames_sent"] > 0
    assert bool(damaged) == damage
    assert c["frames_built"] == len(damaged)


def test_the_clock_never_reaches_a_rounds_results(monkeypatch):
    """Two runs whose recorder clocks are different seeded fake clocks
    give the same round results and the same globals."""
    runs = []
    for seed in (1, 2):
        rng = np.random.default_rng(seed)
        now = [0.0]

        def fake_clock(rng=rng, now=now):
            now[0] += float(rng.exponential(1e-3))
            return now[0]

        monkeypatch.setattr(obs, "clock", fake_clock)
        sim = q8_sim(clients=3, rows=48)
        results = [sim.run_round() for _ in range(2)]
        runs.append((results, sim.server.global_params.copy(),
                     [r["t1"] for r in obs.history()[-2:]]))
    (res_a, g_a, t_a), (res_b, g_b, t_b) = runs
    assert res_a == res_b
    assert g_a.tobytes() == g_b.tobytes()
    assert t_a != t_b                        # the clocks did differ
