"""``chip_smoke.py``'s phases, rehearsed on the CPU at a tiny scale (the
widths stay; rows and entities shrink). ``main`` itself refuses to run
without a TPU — that refusal is tested too."""
import json
import os
import time

import numpy as np
import pytest

import jax

import chip_smoke


@pytest.fixture()
def tiny(monkeypatch, tmp_path):
    """A deployment of 2048 rows / 64 users / 16 items on disk, with the
    chip's window layout: ``training_phase`` builds and RUNS in one call, on
    the CPU, so the layout's policy predicate is patched and no
    ``compiling_for`` is entered (it would turn donation on too)."""
    monkeypatch.setattr(
        "photon_tpu.ops.sparse_windows.windows_pay",
        lambda num_features: num_features >= 1024,
    )
    monkeypatch.setattr(chip_smoke, "SERVE_REQUESTS", 3)
    monkeypatch.setattr(chip_smoke, "SERVE_ROWS_PER_REQ", 8)
    monkeypatch.setattr(chip_smoke, "SCORE_BATCH_ROWS", 64)
    workdir = str(tmp_path)
    sizes = chip_smoke.write_splits(0, 2048, 64, 16, workdir, workers=2)
    assert sizes["rows"] == 2048 and not sizes["reduced"]
    data = chip_smoke.generate(0, 2048, 64, 16)
    return workdir, sizes, data


@pytest.fixture()
def own_arrays_only(monkeypatch):
    """``assert_live_arrays_on`` looks at every live array of the process.
    On a worker that ran another test file first, that file's meshed
    coordinates may still be alive (jit caches keyed on the coordinate keep
    them: ROADMAP C9), and the rehearsal then failed on arrays it never
    made. Hold the phases to the arrays THEY make; ``chip_smoke.py`` itself,
    a process of its own on the chip, keeps looking at all of them."""
    left = jax.live_arrays()  # held here, so none of their ids is reused
    theirs = {id(a) for a in left}
    live_arrays = jax.live_arrays
    monkeypatch.setattr(
        jax, "live_arrays",
        lambda *a, **k: [x for x in live_arrays(*a, **k) if id(x) not in theirs],
    )


def test_train_score_serve_phases(tiny, own_arrays_only, capsys):
    workdir, sizes, data = tiny
    devices = jax.devices()[:1]
    began = time.perf_counter()
    out = chip_smoke.training_phase(
        "train",
        chip_smoke.training_args(workdir, os.path.join(workdir, "train_out")),
        devices=devices, spread=1, fe_dim=chip_smoke.fe_columns(data),
    )
    model_dir = os.path.join(out["output"], "best")
    by_uid = chip_smoke.scoring_phase(
        workdir, model_dir, data, sizes, devices=jax.devices()
    )
    assert len(by_uid) == 2048 // chip_smoke.HELDOUT_DIV
    chip_smoke.serving_phase(workdir, model_dir, by_uid, devices=jax.devices())
    lines = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    assert [row["phase"] for row in lines] == ["train", "score", "serve"]
    compiled = chip_smoke.compiled_since(began)
    assert lines[0]["sweep_compiles"][1] == 0, compiled
    assert lines[2]["compiles_while_serving"] == 0, compiled


def test_meshed_fit_matches_one_device(tiny):
    """The four-chip phase, over the harness's virtual CPU devices."""
    workdir, sizes, data = tiny
    assert len(jax.devices()) >= 4
    chip_smoke.four_chip_phase(workdir, data, sizes, devices=jax.devices())


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_reference_scores_are_the_generators_margin():
    """The plain NumPy scoring, held to the model that drew the labels."""
    d = chip_smoke.generate(3, 256, 8, 4)
    rng = np.random.default_rng(0)
    model = {
        "b_fe": 0.25,
        "w_fe": rng.normal(size=chip_smoke.FE_DIM - 1),
        "w_user": rng.normal(size=(8, chip_smoke.RE_DIM)),
        "w_item": rng.normal(size=(4, chip_smoke.RE_DIM)),
    }
    got = chip_smoke.reference_scores(model, d, 10, 12)
    for out, i in zip(got, (10, 11)):
        want = (
            0.25
            + d["vals"][i] @ model["w_fe"][d["cols"][i]]
            + d["xu"][i] @ model["w_user"][d["user"][i]]
            + d["xi"][i] @ model["w_item"][d["item"][i]]
        )
        assert out == pytest.approx(want)
