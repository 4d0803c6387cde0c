"""serve.main as a library entry point: argv in, served outputs and the
engines out; the compile-cache placement rule; replica placement; and
simulate.main, the simulated cluster's entry point."""
import jax
import pytest

from repro.launch import serve, simulate


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    """Run serve.main with the cache directory given from outside, so the
    test leaves this process's JAX config as it found it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_main_serves_paged_from_argv(cache_env):
    res = serve.main(["--reduced", "--requests", "3",
                      "--max-new", "4"])
    outs = res["outputs"]
    assert sorted(outs) == [0, 1, 2]
    assert all(1 <= len(toks) <= 4 for toks in outs.values())
    (engine,) = res["engines"]
    assert engine.device == jax.devices()[0]


def test_replicas_placed_round_robin_over_devices(cache_env):
    res = serve.main(["--reduced", "--replicas", "2",
                      "--router", "round_robin", "--requests", "4",
                      "--max-new", "3"])
    devs = jax.devices()
    assert [e.device for e in res["engines"]] == [devs[i % len(devs)]
                                                  for i in range(2)]
    assert len(res["outputs"]) == 4


def test_simulate_main_serves_cluster_without_weights(cache_env,
                                                     monkeypatch):
    """The simulated cluster prices every request on LatencyModel replicas
    and builds no model weights: each request finishes or is shed."""
    from repro.models import api

    def no_weights(*a, **kw):
        raise AssertionError("the simulator initialised model weights")

    monkeypatch.setattr(api, "init_params", no_weights)
    res = simulate.main(["--reduced", "--replicas", "2", "--router",
                         "slo_aware", "--requests", "8"])
    assert res.requests
    assert len(res.finished) + len(res.shed) == len(res.requests)
    assert res.peak_replicas == 2


def test_compile_cache_dir_from_env_or_repo(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        serve.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        serve.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(serve.CACHE_DIR)
        # a fixed path at the repo root, never a temp name
        assert (serve.CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
