"""Tests for photon_tpu.util (Timed, PhotonLogger, events, dates, io)."""
import datetime
import logging
import os

import pytest

from photon_tpu.util import (
    DateRange,
    DaysRange,
    Event,
    EventEmitter,
    EventListener,
    PhotonLogger,
    Timed,
    prepare_output_dir,
    resolve_date_range_paths,
    timed,
)


def test_timed_context_and_decorator(caplog):
    with caplog.at_level(logging.INFO, logger="photon_tpu"):
        with Timed("phase-x") as t:
            pass
        assert t.elapsed_s is not None and t.elapsed_s >= 0
        assert any("phase-x" in r.message for r in caplog.records)

        @timed("fn-y")
        def f(a, b):
            return a + b

        assert f(1, 2) == 3
        assert any("fn-y" in r.message for r in caplog.records)


def test_timed_records_elapsed_on_exception(caplog):
    """A failing block still gets its wall measured and logged as
    'failed after' — phase timing must survive the error path."""
    with caplog.at_level(logging.INFO, logger="photon_tpu"):
        with pytest.raises(RuntimeError):
            with Timed("phase-boom") as t:
                raise RuntimeError("mid-phase")
    assert t.elapsed_s is not None and t.elapsed_s >= 0
    assert any(
        "phase-boom" in r.message and "failed after" in r.message
        for r in caplog.records
    )


def test_photon_logger_copies_to_destination(tmp_path):
    dest = tmp_path / "logs" / "job.log"
    with PhotonLogger(dest, level="debug") as log:
        log.info("hello %d", 42)
        log.debug("dbg")
        log.error("bad")
    text = dest.read_text()
    assert "hello 42" in text and "dbg" in text and "bad" in text
    # idempotent close
    log.close()


def test_photon_logger_creates_missing_destination_dirs(tmp_path):
    """close() must create the destination's parent directories (the
    reference copies to HDFS paths that may not exist yet) and remove
    its temp buffer."""
    dest = tmp_path / "a" / "b" / "c" / "job.log"
    log = PhotonLogger(dest)
    tmp_buffer = log._tmp_path
    log.info("deep %s", "copy")
    log.close()
    assert "deep copy" in dest.read_text()
    assert not os.path.exists(tmp_buffer)


def test_event_emitter_failing_listener_does_not_block_later_ones():
    """Isolation must hold regardless of registration order: a listener
    registered BEFORE the failing one and one registered AFTER both see
    every event."""
    before, after = [], []
    emitter = EventEmitter()
    emitter.register(lambda e: before.append(e.name))

    class Boom(EventListener):
        def on_event(self, event: Event) -> None:
            raise RuntimeError("listener bug")

    emitter.register(Boom())
    emitter.register(lambda e: after.append(e.name))
    emitter.emit("setup")
    emitter.emit("training_finish")
    assert before == ["setup", "training_finish"]
    assert after == ["setup", "training_finish"]
    emitter.close()


def test_event_emitter_dispatch_and_isolation():
    seen = []
    emitter = EventEmitter()
    emitter.register(lambda e: seen.append(e))

    class Boom(EventListener):
        def on_event(self, event: Event) -> None:
            raise RuntimeError("listener bug")

    emitter.register(Boom())
    emitter.emit("training_start", task="logistic")
    assert len(seen) == 1
    assert seen[0].name == "training_start"
    assert seen[0].payload["task"] == "logistic"
    emitter.close()
    emitter.emit("after_close")
    assert len(seen) == 1


def test_date_range_parse_and_days():
    r = DateRange.parse("20260101-20260103")
    assert [d.day for d in r.dates()] == [1, 2, 3]
    with pytest.raises(ValueError):
        DateRange.parse("20260103-20260101")
    with pytest.raises(ValueError):
        DateRange.parse("2026-01-01")

    dr = DaysRange.parse("3-1").to_date_range(today=datetime.date(2026, 1, 10))
    assert dr.start == datetime.date(2026, 1, 7)
    assert dr.end == datetime.date(2026, 1, 9)
    with pytest.raises(ValueError):
        DaysRange.parse("1-3")


def test_resolve_date_range_paths(tmp_path):
    for day in ("01", "02"):
        os.makedirs(tmp_path / "daily" / "2026" / "01" / day)
    r = DateRange.parse("20260101-20260103")
    paths = resolve_date_range_paths(tmp_path, r)
    assert len(paths) == 2
    assert paths[0].endswith("daily/2026/01/01")
    with pytest.raises(FileNotFoundError):
        resolve_date_range_paths(tmp_path / "nope", r)


def test_prepare_output_dir(tmp_path):
    out = tmp_path / "out"
    prepare_output_dir(out)
    (out / "stale").write_text("x")
    with pytest.raises(FileExistsError):
        prepare_output_dir(out)
    prepare_output_dir(out, override=True)
    assert os.path.isdir(out) and not os.listdir(out)


def test_put_with_retry_transient_then_success(caplog):
    """Transient UNAVAILABLE placements retry with backoff; other errors
    propagate immediately (photon_tpu/util/device_retry.py)."""
    from photon_tpu.util.device_retry import put_with_retry

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")
        return "ok"

    assert put_with_retry(flaky, attempts=3, backoff_s=0.0) == "ok"
    assert calls["n"] == 3

    def hard():
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        put_with_retry(hard, attempts=3, backoff_s=0.0)

    def always():
        raise RuntimeError("UNAVAILABLE: still down")

    with pytest.raises(RuntimeError):
        put_with_retry(always, attempts=2, backoff_s=0.0)
