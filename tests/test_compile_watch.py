"""``compile_watch``'s per-program table: which program compiled, how
often, whether the persistent cache served it, and after which instant —
beside the six totals, which keep their meaning."""
import threading
import time

import pytest

import jax
import jax.numpy as jnp

from photon_tpu.util import compile_watch


@pytest.fixture()
def inputs():
    """Built before any watch: eager ops compile tiny programs of their own."""
    compile_watch.install()
    x = jnp.arange(16, dtype=jnp.float32)
    return x, x + 1.0


def test_two_jits_give_two_named_rows_and_a_second_call_none(inputs):
    x, y = inputs

    @jax.jit
    def watch_table_alpha(v):
        return jnp.tanh(v) * 3.0

    @jax.jit
    def watch_table_beta(v):
        return jnp.cos(v) - 1.0

    t0 = time.perf_counter()
    before = compile_watch.snapshot()
    watch_table_alpha(x).block_until_ready()
    watch_table_beta(x).block_until_ready()
    rows = compile_watch.programs_since(t0)
    named = {n: r for n, r in rows.items() if "watch_table" in n}
    assert set(named) == {"jit(watch_table_alpha)", "jit(watch_table_beta)"}
    for row in named.values():
        assert row["compiles"] == 1 and row["backend_compile_s"] > 0
        assert row["trace_s"] > 0 and row["lowering_s"] > 0
        assert row["last_t"] >= t0
    # the rows sum to the totals' delta: the six totals mean what they did
    delta = compile_watch.delta(before)
    assert set(delta) == {
        "backend_compiles", "backend_compile_s", "cache_hits",
        "cache_misses", "trace_s", "lowering_s",
    }
    assert sum(r["compiles"] for r in rows.values()) == delta["backend_compiles"]
    for key in ("backend_compile_s", "trace_s", "lowering_s"):
        assert sum(r[key] for r in rows.values()) == pytest.approx(
            delta[key], abs=1e-3
        )
    # the whole-process table holds them too, under the same names
    table = compile_watch.programs()
    assert table["jit(watch_table_alpha)"]["compiles"] == 1

    t1 = time.perf_counter()
    watch_table_alpha(y).block_until_ready()
    watch_table_beta(y).block_until_ready()
    assert not any("watch_table" in n for n in compile_watch.programs_since(t1))
    assert compile_watch.programs()["jit(watch_table_alpha)"]["compiles"] == 1
    assert "jit(watch_table_alpha) x1" in compile_watch.describe(named)
    assert compile_watch.describe({}) == "no program"


def test_a_recompile_served_by_the_persistent_cache_is_counted_as_such(
    inputs, tmp_path
):
    from jax.experimental.compilation_cache import compilation_cache

    x, _ = inputs
    saved = {
        k: getattr(jax.config, k)
        for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:

        @jax.jit
        def watch_table_cached(v):
            return jnp.sinh(v) + 2.0

        t0 = time.perf_counter()
        watch_table_cached(x).block_until_ready()
        cold = compile_watch.programs_since(t0)["jit(watch_table_cached)"]
        assert (cold["compiles"], cold["cache_served"]) == (1, 0)
        assert cold["cache_served_s"] == 0.0

        jax.clear_caches()  # the in-memory executables; the directory stays
        t1 = time.perf_counter()
        watch_table_cached(x).block_until_ready()
        warm = compile_watch.programs_since(t1)["jit(watch_table_cached)"]
        assert (warm["compiles"], warm["cache_served"]) == (1, 1)
        assert warm["cache_served_s"] == warm["backend_compile_s"] > 0
        whole = compile_watch.programs()["jit(watch_table_cached)"]
        assert (whole["compiles"], whole["cache_served"]) == (2, 1)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_thread_scope_still_attributes_per_thread(inputs):
    x, _ = inputs
    got = {}

    def work(name, fn):
        with compile_watch.thread_scope() as acc:
            jax.jit(fn)(x).block_until_ready()
        got[name] = acc

    def watch_table_thread_a(v):
        return jnp.exp(v) * 0.5

    def watch_table_thread_b(v):
        return jnp.log1p(v) * 0.25

    with compile_watch.thread_scope() as outer:
        threads = [
            threading.Thread(target=work, args=("a", watch_table_thread_a)),
            threading.Thread(target=work, args=("b", watch_table_thread_b)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    assert got["a"]["backend_compiles"] == 1 == got["b"]["backend_compiles"]
    assert outer["backend_compiles"] == 0  # nothing compiled on this thread
    table = compile_watch.programs()
    assert table["jit(watch_table_thread_a)"]["compiles"] == 1
    assert table["jit(watch_table_thread_b)"]["compiles"] == 1


def test_the_table_is_bounded(monkeypatch):
    compile_watch.install()
    monkeypatch.setattr(compile_watch, "MAX_PROGRAMS", len(compile_watch.programs()))
    event = "/jax/core/compile/backend_compile_duration"
    t0 = time.perf_counter()
    compile_watch._on_duration(event, 0.25, fun_name="jit(watch_table_overflow)")
    rows = compile_watch.programs_since(t0)
    assert "jit(watch_table_overflow)" not in compile_watch.programs()
    assert rows[compile_watch.OVERFLOW_ROW]["compiles"] == 1
    assert rows[compile_watch.OVERFLOW_ROW]["backend_compile_s"] == 0.25
