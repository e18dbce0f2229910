"""``chip_smoke.py``'s phases on CPU at ``reduced()`` size, plus the serve
entry points it relies on (``--layers``/``depth_cut``, ``main(argv)``, the
non-zero exit on a failed background save).

Kernels run in Pallas interpret mode here; the script itself refuses to
run anywhere but a TPU, which the first tests pin down.
"""
import dataclasses
import importlib.util
import threading
from pathlib import Path

import jax
import pytest

from repro.configs import ARCHS, depth_cut, get_config
from repro.launch import serve

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_cache_dir(monkeypatch):
    """serve.main enables the persistent compile cache; keep this test
    process's JAX config untouched."""
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""      # no phase ran, no result line


def test_device_phase_refuses_interpret_routing(smoke):
    # on a CPU backend the kernels would run interpreted: the phase that
    # guards against a hidden fallback must say so
    with pytest.raises(AssertionError, match="compiled kernels"):
        smoke.phase_device(jax.devices()[0])


def test_kernel_phase_interpret(smoke):
    errs = smoke.phase_kernels(batch=2, kv=2, group=8, hd=128, t=512, nb=16,
                               interpret=True)
    assert set(errs) == {"decode", "extend"}
    assert max(errs.values()) <= smoke.KERNEL_TOL


def test_serve_and_logit_phases_reduced(smoke, monkeypatch, no_cache_dir):
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "1")
    monkeypatch.setenv("REPRO_EXTEND_KERNEL", "1")
    argv = ["--arch", "deepseek-67b", "--reduced", "--sessions", "4",
            "--shared-docs", "2", "--doc-len", "192", "--requests", "2",
            "--new-tokens", "3", "--chunk-tokens", "32"]
    mgr = smoke.phase_serve(argv)
    assert mgr.decode_mode == "kernel" and mgr.extend_mode == "kernel"
    errs = smoke.phase_logits(mgr, prefix=64)
    # an f32 model has no bf16 floor: every path agrees to f32 rounding
    assert max(errs.values()) <= smoke.F32_NOISE


def test_serve_phase_rejects_fallback_routing(smoke, monkeypatch, no_cache_dir):
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "blocked")
    argv = ["--arch", "deepseek-67b", "--reduced", "--sessions", "2",
            "--shared-docs", "2", "--doc-len", "96", "--requests", "1",
            "--new-tokens", "2", "--chunk-tokens", "32"]
    with pytest.raises(AssertionError, match="did not route"):
        smoke.phase_serve(argv)


def test_analytics_phase_small(smoke):
    errs = smoke.phase_analytics(rows=4096, chunk=1024)
    assert errs["logreg_w"] <= smoke.SGD_TOL
    assert max(v for k, v in errs.items() if k != "logreg_w") <= smoke.STATS_TOL


# ---------------------------------------------------------------------------
# depth_cut / --layers
# ---------------------------------------------------------------------------

def _valid_depth(cfg):
    from repro.configs import _structural_period

    period = _structural_period(cfg)
    lead = cfg.moe.first_dense_layers if cfg.moe is not None else 0
    return period * (lead // period + 1)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_depth_cut_keeps_every_width(arch):
    cfg = get_config(arch)
    n = _valid_depth(cfg)
    cut = depth_cut(cfg, n)
    assert cut.n_layers == n and cut.name == f"{arch}-{n}L"
    changed = {f.name for f in dataclasses.fields(cfg)
               if getattr(cfg, f.name) != getattr(cut, f.name)}
    assert changed <= {"name", "n_layers"}


def test_depth_cut_rejects_partial_periods():
    with pytest.raises(ValueError, match="whole periods"):
        depth_cut(get_config("jamba-v0.1-52b"), 3)     # period 8
    with pytest.raises(ValueError, match="whole periods"):
        depth_cut(get_config("deepseek-67b"), 0)
    with pytest.raises(ValueError, match="leading dense"):
        depth_cut(get_config("deepseek-v2-236b"), 1)   # 1 leading dense layer


def test_serve_layers_flag_cuts_depth_only(no_cache_dir, capsys):
    argv = ["--arch", "deepseek-67b", "--reduced", "--layers", "1",
            "--doc-len", "64", "--requests", "1", "--new-tokens", "2",
            "--chunk-tokens", "32"]
    eng = serve.main(argv)
    assert eng.model.cfg.n_layers == 1
    assert eng.model.cfg.d_model == 64            # widths of the preset kept
    assert "model deepseek-67b-smoke-1L" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# a failed background save fails the run
# ---------------------------------------------------------------------------

def test_serve_exits_nonzero_on_failed_background_save(tmp_path, monkeypatch,
                                                       no_cache_dir):
    from repro.core.store import PinnedStore

    real_write = PinnedStore._write_snapshot

    def write_fails_off_main_thread(self, *a, **kw):
        # the background writer's saves fail; the final, synchronous save
        # on the serving thread succeeds
        if threading.current_thread() is not threading.main_thread():
            raise OSError("disk full")
        return real_write(self, *a, **kw)

    monkeypatch.setattr(PinnedStore, "_write_snapshot",
                        write_fails_off_main_thread)
    argv = ["--arch", "deepseek-67b", "--reduced", "--doc-len", "64",
            "--requests", "2", "--new-tokens", "2", "--chunk-tokens", "32",
            "--store-dir", str(tmp_path / "kv"), "--snapshot-every", "1"]
    with pytest.raises(SystemExit) as exc:
        serve.main(argv)
    assert exc.value.code not in (0, None)
    assert "background snapshot save" in str(exc.value.code)


def test_serve_exits_zero_when_saves_succeed(tmp_path, no_cache_dir):
    argv = ["--arch", "deepseek-67b", "--reduced", "--doc-len", "64",
            "--requests", "2", "--new-tokens", "2", "--chunk-tokens", "32",
            "--store-dir", str(tmp_path / "kv"), "--snapshot-every", "1"]
    eng = serve.main(argv)
    assert eng.store.bg_saves >= 1 and not eng.store.save_errors


def test_compile_cache_dir_honours_env(tmp_path, monkeypatch):
    from repro.launch import compile_cache

    seen = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == tmp_path
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.use_compile_cache() == ROOT / ".jax_cache"
    assert seen == [("jax_compilation_cache_dir", str(tmp_path)),
                    ("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))]
    ignored = {ln.strip().strip("/")
               for ln in (ROOT / ".gitignore").read_text().splitlines()}
    assert ".jax_cache" in ignored
