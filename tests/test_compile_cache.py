"""The one compile-cache rule (``ramses_tpu/platform.py``).

``JAX_COMPILATION_CACHE_DIR`` set ⇒ JAX already uses it and no code
path touches ``jax_compilation_cache_dir`` (package import, namelist
key, serve loop, ``force_cpu_mesh``).  Unset ⇒ the default is the fixed
``<checkout>/.jax_cache``; an operator's namelist ``compile_cache_dir``
replaces it by name — honored on CPU-forced runs too, since the
operator asked for it — and CPU-forced processes are otherwise
uncached.  These tests pin the plumbing only (config update, the
standard variable's precedence, stats surface, fail-soft on a bad
path); actual cache hits are a backend concern exercised on TPU
(``chip_smoke.py`` run twice).
"""

import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ramses_tpu import platform
from ramses_tpu.config import params_from_string

pytestmark = pytest.mark.smoke

MINI = """
&RUN_PARAMS
hydro=.true.
{extra}
/
&AMR_PARAMS
levelmin=3
levelmax=3
/
"""


def _params(extra=""):
    return params_from_string(MINI.format(extra=extra), ndim=2)


@pytest.fixture
def restore_jax_cache_config():
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_enable_xla_caches")
    old = {k: getattr(jax.config, k) for k in keys}
    olddir = platform._CACHE_STATS["dir"]
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    platform._CACHE_STATS["dir"] = olddir


def test_explicit_dir_configures_jax(tmp_path, restore_jax_cache_config):
    import jax

    d = str(tmp_path / "xla_cache")
    p = _params(f"compile_cache_dir='{d}'")
    got = platform.setup_compile_cache(p)
    assert got == d
    assert os.path.isdir(d)
    assert jax.config.jax_compilation_cache_dir == d
    assert platform.compile_cache_stats()["dir"] == d


def test_unset_leaves_cache_alone(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert platform.setup_compile_cache(_params()) == ""


def test_standard_variable_outranks_namelist(tmp_path, monkeypatch,
                                             restore_jax_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, a namelist key reports the
    variable's directory as the one in effect and leaves
    ``jax_compilation_cache_dir`` exactly as it was."""
    import jax

    d = str(tmp_path / "env_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    before = jax.config.jax_compilation_cache_dir
    d2 = str(tmp_path / "nml_cache")
    p = _params(f"compile_cache_dir='{d2}'")
    assert platform.setup_compile_cache(p) == d
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(d2)
    assert platform.compile_cache_stats()["dir"] == d


def test_bad_path_warns_and_runs_uncached(tmp_path,
                                          restore_jax_cache_config):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("x")
    p = _params(f"compile_cache_dir='{blocker}/sub'")
    with pytest.warns(UserWarning, match="not usable"):
        assert platform.setup_compile_cache(p) == ""


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax
seen = [jax.config.jax_compilation_cache_dir]
import ramses_tpu
from ramses_tpu import platform
from ramses_tpu.config import params_from_string
seen.append(jax.config.jax_compilation_cache_dir)
p = params_from_string("&RUN_PARAMS\\ncompile_cache_dir='{nml}'\\n/",
                       ndim=2)
platform.setup_compile_cache(p)
seen.append(jax.config.jax_compilation_cache_dir)
platform.force_cpu_mesh(2)
seen.append(jax.config.jax_compilation_cache_dir)
print('SEEN', *seen, sep='|')
"""


def _fresh(code, **env_over):
    """Run ``code`` in a fresh interpreter that is not CPU-forced and
    return the fields of its ``SEEN|...`` line."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS",
                        "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO
    env.update(env_over)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    line = [l for l in out.splitlines() if l.startswith("SEEN|")][-1]
    return line.split("|")[1:]


def test_standard_variable_is_never_touched(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set ⇒ the config value is the same
    before package import, after it, after ``setup_compile_cache`` with
    a namelist key, and after ``force_cpu_mesh``."""
    d = str(tmp_path / "std")
    nml = str(tmp_path / "nml")
    seen = _fresh(_PROBE.format(nml=nml), JAX_COMPILATION_CACHE_DIR=d)
    assert seen == [d] * 4
    assert not os.path.exists(nml)


def test_default_is_checkout_jax_cache():
    """Unset, on an interpreter that is not CPU-forced: the import
    default is ``<checkout>/.jax_cache``, identical across two
    interpreters (no $HOME, temp name, pid or time in it).  Config
    only — no backend is initialised, so this runs anywhere."""
    code = ("import jax, ramses_tpu;"
            "print('SEEN', jax.config.jax_compilation_cache_dir,"
            " sep='|')")
    a = _fresh(code, HOME="/nonexistent/a")
    b = _fresh(code, HOME="/nonexistent/b", TMPDIR="/tmp")
    assert a == b == [os.path.join(REPO, ".jax_cache")]
