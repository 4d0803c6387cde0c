"""Serving simulator: end-to-end sanity and the UELLM-vs-baseline orderings
the paper claims (directionally, on the simulated paper cluster)."""
import copy

import pytest

from repro.configs import get_config
from repro.core import (LengthPredictor, Monitor, ResourceProfiler, bgs,
                        get_scheduler, helr)
from repro.core.profiler import PredictorConfig
from repro.core.scheduler import SchedulerConfig
from repro.core.types import DeviceNode
from repro.data.workload import WorkloadConfig, gen_requests, train_pairs
from repro.serving import morphling_deploy_overhead, simulate


# ----------------------------------------------------------------- simulator

@pytest.fixture(scope="module")
def sim_setup():
    model = get_config("chatglm2-6b")
    pred = LengthPredictor(PredictorConfig(), seed=0)
    toks, lens = train_pairs(WorkloadConfig(), 512, seed=1)
    pred.fit(toks, lens, epochs=12)
    perf = [35e12, 25e12, 30e12, 15e12]     # fastest pair spans a NODE link
    nodes = [DeviceNode(i, memory=10e9, performance=perf[i]) for i in range(4)]
    pix, nd = 5e-5, 2e-4
    lat = [[0, pix, nd, nd], [pix, 0, nd, nd],
           [nd, nd, 0, pix], [nd, nd, pix, 0]]
    wl = gen_requests(WorkloadConfig(n_requests=96, arrival_rate=24.0, seed=7))
    return model, pred, nodes, lat, wl


def _run(sim_setup, sched, deploy, overhead=0.0):
    model, pred, nodes, lat, wl = sim_setup
    prof = ResourceProfiler(copy.deepcopy(pred), model)
    mon = Monitor(prof)
    rs = [copy.deepcopy(r) for r in wl]
    return simulate(rs, model, get_scheduler(sched), SchedulerConfig(),
                    profiler=prof, monitor=mon, deploy=deploy,
                    deploy_overhead=overhead, nodes=nodes, latency=lat)


def test_simulator_conserves_requests(sim_setup):
    out = _run(sim_setup, "slo-odbs", helr)
    assert all(r.finish_time is not None for r in out.requests)
    assert out.throughput > 0
    assert 0 <= out.slo_violation_rate <= 1


def test_ua_beats_fifo_on_slo(sim_setup):
    ua = _run(sim_setup, "slo-odbs", helr)
    fifo_ = _run(sim_setup, "fifo", helr)
    assert ua.slo_violation_rate <= fifo_.slo_violation_rate + 1e-9


def test_helr_not_worse_than_bgs(sim_setup):
    ua = _run(sim_setup, "slo-odbs", helr)
    ub = _run(sim_setup, "slo-odbs", bgs)
    assert ua.avg_latency <= ub.avg_latency * 1.05


def test_morphling_overhead_costs_latency(sim_setup):
    model, pred, nodes, lat, wl = sim_setup
    oh = morphling_deploy_overhead(model, nodes, lat)
    assert oh > 0
    mor = _run(sim_setup, "fifo", helr, overhead=oh)
    ud = _run(sim_setup, "fifo", helr)
    assert mor.avg_latency > ud.avg_latency
