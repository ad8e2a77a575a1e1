"""``util/target``: the one answer to "which platform is this program for",
and what hangs on it: the window layout's policy, the gather, donation,
the lowered sparse pass. Everything here traces, lowers or runs ONE sparse
pass; no sweep or score program runs inside a ``compiling_for`` (XLA:CPU
corrupts donated buffers)."""
import os
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.ops import gather, sparse_windows
from photon_tpu.util import target

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "photon_tpu")


def test_platform_is_the_default_backend_outside_a_context():
    assert target.platform() == jax.default_backend()


def test_override_nests_and_is_restored():
    outside = target.platform()
    with target.compiling_for("tpu"):
        assert target.platform() == "tpu"
        with target.compiling_for("gpu"):
            assert target.platform() == "gpu"
        assert target.platform() == "tpu"
    assert target.platform() == outside


def test_override_is_restored_after_an_exception():
    outside = target.platform()
    with pytest.raises(RuntimeError, match="boom"):
        with target.compiling_for("tpu"):
            with target.compiling_for("gpu"):
                raise RuntimeError("boom")
    assert target.platform() == outside


def test_a_thread_started_inside_the_context_sees_it():
    """Process-wide, like the variables it replaced: the parallel precompile
    (game/descent.precompile_coordinates) traces on pool threads."""
    seen = {}

    def ask(key):
        seen[key] = target.platform()

    with target.compiling_for("tpu"):
        t = threading.Thread(target=ask, args=("inside",))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    t = threading.Thread(target=ask, args=("outside",))
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert seen == {"inside": "tpu", "outside": jax.default_backend()}


def test_contexts_left_out_of_order_leave_nothing_behind():
    """Two threads' contexts need not close innermost first; each takes its
    own entry away, and the last one out restores the default."""
    first, second = target.compiling_for("tpu"), target.compiling_for("gpu")
    first.__enter__()
    second.__enter__()
    first.__exit__(None, None, None)
    assert target.platform() == "gpu"
    second.__exit__(None, None, None)
    assert target.platform() == jax.default_backend()


def _ell(n, k, d, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.standard_normal((n, k)).astype(np.float32)
    return idx, val


@pytest.mark.parametrize("d,built", [(1024, True), (1023, False)])
def test_windows_are_built_for_a_tpu_from_1024_columns(d, built):
    idx, val = _ell(64, 4, d)
    assert sparse_windows.maybe_build_windows(idx, val, d) is None
    with target.compiling_for("tpu"):
        windows = sparse_windows.maybe_build_windows(idx, val, d)
    assert (windows is not None) == built
    if built:
        assert isinstance(windows, sparse_windows.ColumnWindows)
        assert windows.window == 128  # build_column_windows' default


def test_gather_fetches_rows_for_a_tpu_alone():
    table = jnp.arange(300, dtype=jnp.float32)
    idx = jnp.asarray([[0, 299], [128, 5]], jnp.int32)

    def lowered():
        # a jit of its own: a trace is cached by function, and the platform
        # is read while tracing
        take = jax.jit(lambda t, i: gather.take_1d(t, i))
        return take.lower(table, idx).as_text(debug_info=True)

    assert not gather.fetches_rows()
    plain = lowered()
    assert "photon.gather.fetch" not in plain
    with target.compiling_for("tpu"):
        assert gather.fetches_rows()
        fetched = lowered()
        got = np.asarray(gather.take_1d(table, idx))
    assert "photon.gather.fetch" in fetched
    assert "photon.gather.select" in fetched
    assert np.array_equal(got, np.asarray(table)[np.asarray(idx)])


def test_donation_follows_the_platform():
    assert target.donation_enabled() == (jax.default_backend() != "cpu")
    with target.compiling_for("tpu"):
        assert target.donation_enabled()
        with target.compiling_for("cpu"):
            assert not target.donation_enabled()
    assert target.donation_enabled() == (jax.default_backend() != "cpu")


def _sparse_value_and_gradient_text(windows):
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.types import SparseBatch

    n, k, d = 256, 6, 2048
    idx, val = _ell(n, k, d, seed=1)
    batch = SparseBatch(
        indices=jnp.asarray(idx),
        values=jnp.asarray(val),
        labels=jnp.ones((n,), jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
        windows=windows(idx, val, d),
    )
    objective = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    # a jit of its own (a trace is cached by function)
    lowered = jax.jit(lambda w, b: objective.value_and_gradient(w, b)).lower(
        jnp.zeros((d,), jnp.float32), batch
    )
    return lowered.as_text(debug_info=True)


def test_sparse_pass_lowers_as_the_chip_takes_it_inside_the_context():
    with target.compiling_for("tpu"):
        text = _sparse_value_and_gradient_text(
            sparse_windows.maybe_build_windows
        )
    assert "photon.rmatvec.prefix" in text
    assert "photon.gather.fetch" in text


def test_sparse_pass_lowers_as_the_cpu_takes_it_outside_the_context():
    text = _sparse_value_and_gradient_text(sparse_windows.maybe_build_windows)
    assert "photon.rmatvec.prefix" not in text
    assert "photon.gather.fetch" not in text
    assert "photon.rmatvec" in text  # the segment_sum, under the pass's scope


def test_a_layout_in_the_batch_decides_the_backward_pass_on_any_platform():
    """A test that RUNS on the CPU what the chip runs builds the layout
    inside the context and runs outside it: prefix sums over a plain
    gather, no row fetch, no donation."""
    with target.compiling_for("tpu"):
        build = sparse_windows.maybe_build_windows
        idx, val = _ell(8, 2, 2048)
        assert build(idx, val, 2048) is not None
    text = _sparse_value_and_gradient_text(
        lambda idx, val, d: sparse_windows.build_column_windows(idx, val, d)
    )
    assert "photon.rmatvec.prefix" in text
    assert "photon.gather.fetch" not in text


#: the switches PR 34 took away, which may not come back under
#: ``photon_tpu/`` (spelled in pieces: a grep for them finds no file)
_GONE = re.compile(
    "PHOTON_(?:"
    + "|".join(["SPARSE_", "GLM_LINE" + "SEARCH", r"\w*_DONATION", "NATIVE_" + "WINDOWS"])
    + ")"
)


def _package_sources():
    for folder, _, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    yield os.path.relpath(path, PACKAGE), f.read()


def test_no_removed_switch_is_left_in_the_package():
    left = {
        rel: sorted(set(_GONE.findall(text)))
        for rel, text in _package_sources()
        if _GONE.search(text)
    }
    assert not left, left


def test_the_default_backend_is_asked_in_one_place():
    asks = sorted(
        rel for rel, text in _package_sources() if "default_backend()" in text
    )
    assert asks == [os.path.join("util", "target.py")], asks


def test_no_environment_read_in_the_sparse_pass():
    for rel in ("sparse_windows.py", "gather.py", "objective.py"):
        with open(os.path.join(PACKAGE, "ops", rel)) as f:
            text = f.read()
        assert "os.environ" not in text and "import os" not in text, rel


def test_compiling_for_is_entered_by_no_code_of_the_package():
    enters = sorted(
        rel
        for rel, text in _package_sources()
        if "compiling_for(" in text and rel != os.path.join("util", "target.py")
    )
    assert not enters, enters
