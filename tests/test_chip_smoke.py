"""``chip_smoke.py``'s phases on CPU at tiny sizes (kernels interpreted):
the checks it relies on catch wrong answers, a kernel-mode lowering error
surfaces instead of being demoted away, and the compile-cache helper
honours ``JAX_COMPILATION_CACHE_DIR``."""
import jax
import pytest

import chip_smoke as cs
from repro.api import MaxflowProblem, Solver
from repro.runtime import cache


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


@pytest.fixture(scope="module")
def tiny():
    return cs.washington(12, 5)


def test_phases_pass_on_tiny_instances(clock, tiny):
    g, s, t = tiny
    want = cs.scipy_maxflow(g, s, t)
    cs.phase_kernels(clock, g, s, t)
    for mode in ("vc",) + cs.KERNEL_MODES:
        rec = cs.phase_one_shot(clock, g, s, t, mode, want)
        assert rec["value"] == want
    cs.phase_batched(clock, cs.batched_instances(4))


def test_reference_check_catches_a_wrong_value(clock, tiny):
    g, s, t = tiny
    want = cs.scipy_maxflow(g, s, t)
    with pytest.raises(cs.SmokeFailure, match="expected"):
        cs.phase_one_shot(clock, g, s, t, "vc", want + 1)


def test_certificate_catches_a_broken_flow(tiny, monkeypatch):
    g, s, t = tiny
    sol = Solver().solve(MaxflowProblem(g, s, t))
    flows = sol.flows().copy()
    flows[0] += 1
    monkeypatch.setattr(sol, "flows", lambda: flows)
    with pytest.raises(cs.SmokeFailure):
        cs.check_certificate(g, s, t, sol)


def test_served_wrong_answer_fails(clock, monkeypatch):
    monkeypatch.setattr(cs, "scipy_maxflow", lambda g, s, t: -1)
    with pytest.raises(cs.SmokeFailure, match="scipy"):
        cs.phase_serving(clock, num_requests=4)


def test_lowering_error_in_kernel_mode_is_raised_not_demoted(monkeypatch):
    """A kernel that fails to lower must fail the flush: the degradation
    ladder absorbs dispatch faults only, never trace/lower/compile
    errors."""
    from repro.kernels import window
    from repro.serving.maxflow_service import MaxflowService, ServiceConfig

    class ForcedLoweringError(Exception):
        pass

    def refuse(*args, **kwargs):
        raise ForcedLoweringError("kernel refused")

    jax.clear_caches()  # make the kernel wrappers trace again
    monkeypatch.setattr(window, "windowed_reduce", refuse)
    svc = MaxflowService(ServiceConfig(mode="vc_kernel"))
    g, s, t = cs.washington(8, 4)
    svc.submit(g, s, t)
    with pytest.raises(ForcedLoweringError):
        svc.flush()
    rb = svc.stats()["robustness"]
    assert rb["transient_demotions"] == 0 and rb["host_fallbacks"] == 0
    jax.clear_caches()


def test_main_refuses_a_backend_that_is_not_a_tpu(capsys):
    """On the CPU backend the script stops before any phase and prints no
    result line."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(SystemExit) as exc:
            cs.main(["--cols", "2"])
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_honours_env(tmp_path, monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    try:
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(cache.ENV_VAR)
        assert cache.enable_compile_cache() == str(cache.DEFAULT_DIR)
        assert cache.DEFAULT_DIR.name == ".jax_cache"
        assert (cache.DEFAULT_DIR.parent / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
