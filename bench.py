"""photon-tpu benchmark: GLM/GLMix training throughput on one chip.

Covers all five BASELINE.md configs:
  1. a1a-shaped logistic regression, L-BFGS + L2      (reference demo workload)
  2. linear regression, TRON + L2                     (Hessian-vector path)
  3. Poisson elastic-net OWLQN, sparse d=2^20 ELL     (sparse high-dim path)
  4. GLMix FE + per-user RE via GameEstimator.fit     (REAL framework path,
     skewed entities — bucketing, padding, scatter scoring, CD control flow)
  5. Full GAME: sparse FE + per-user RE (2^20 users) + per-item RE
     (CTR shape; the scale demonstration for the entity axis)

Prints a cumulative JSON result line after EVERY config — the LAST stdout
line is always the most complete parseable result — and mirrors it to
``BENCH_partial.json``. rc=0 if at least one config produced a number.

Robustness (two early rounds of numbers were lost to transient backend
errors): every config runs in its OWN killable subprocess
(``bench.py --config NAME``) with a timeout and per-config retries, so a
hung backend or a transient error costs one config's attempt, never the
round. The TPU probe additionally runs before
anything else (backend init can HANG, not just fail; only a subprocess
timeout recovers from that). Where the probe or a config finds no chip the
orchestrator returns 1 and publishes nothing: a CPU number is never
published in a chip number's place.

Honesty rules (VERDICT round 1):
  - Work is counted from the optimizers' exact on-device eval counters
    (`OptimizeResult.n_evals` / `n_hvp`) — no estimated line-search factors.
  - FLOPs are analytic: a dense GLM value+gradient evaluation on [N, D] is
    two matmuls = 4·N·D flops; Hv likewise. A sparse-ELL evaluation is
    4·N·K flops (K slots/row) plus gather/scatter traffic, so for config 3
    the honest roofline metric is achieved bytes/sec, reported alongside.
  - MFU is achieved-flops / device peak for the matmul dtype actually used.
  - Wall-clock-to-converge is measured at the reference's own tolerances
    (LBFGS tol=1e-7 / maxIter=100, LBFGS.scala:154-156; TRON tol=1e-5 /
    maxIter=15, TRON.scala:256-276) on a post-compile run.
  - GAME throughput (configs 4, 5) counts only REAL samples (padding lanes
    excluded): FE examples = N_real · n_evals; RE examples =
    Σ_entities active_rows(e) · n_evals(e), both from device counters.

vs_baseline: the reference publishes no numbers (BASELINE.md), so this is
measured-TPU ÷ modeled-Spark — the headline examples/sec/chip divided by
the per-executor rate of the analytic per-iteration Spark cost model in
``spark_cost_model.py`` (aggregator hot-loop flops + coefficient broadcast
+ depth-1 treeAggregate + job overhead, per config from its recorded
shape and our on-device eval counters; GAME configs add the RE shuffle
join + local solves per sweep). All model constants are generous to
Spark, so the reported number is a lower bound on "Spark executors
replaced per chip". Full derivation + anchors: BASELINE.md; the output
records the basis (`vs_baseline_basis`) and each config's modeled rate
(`spark_model`).

Benchmark data for configs 1-2 is generated ON DEVICE with jax.random:
host→device transfer of a multi-hundred-MB block would measure the
host link, not the chip (the one-time upload is outside the timed
region either way). Config 3 generates on HOST: its column-window layout
(ops/sparse_windows.py) requires a host-side sort of the static indices,
and the upload cost is reported separately (``upload_s``). Configs 4-5
exercise the real ingest path (host GameData → coordinate build → device),
so their one-time build cost is reported separately from steady-state
sweep throughput.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import spark_cost_model

VS_BASELINE_BASIS = spark_cost_model.basis_string()


def _spark_model_for(name: str, cfg: dict) -> dict | None:
    """Modeled Spark per-executor throughput for one finished config, from
    its RECORDED shape and on-device eval counters (spark_cost_model.py).
    Returns None when the config lacks the fields (failed/partial runs)."""
    try:
        if name == "a1a_logistic_lbfgs":
            rate = spark_cost_model.examples_per_sec_per_executor(
                cfg["n"], 14.0, cfg["d"], cfg["n_evals"]
            )
        elif name == "linear_tron":
            rate = spark_cost_model.examples_per_sec_per_executor(
                cfg["n"], float(cfg["d"]), cfg["d"], cfg["n_evals"],
                cfg.get("n_hvp", 0),
            )
        elif name == "sparse_poisson_owlqn":
            rate = spark_cost_model.examples_per_sec_per_executor(
                cfg["n"], float(cfg["nnz_per_row"]), cfg["d"], cfg["n_evals"]
            )
        elif name in ("glmix_game_estimator", "game_ctr_scale"):
            # model the same measured window examples_per_sec covers:
            # measured_sweeps coordinate-descent sweeps, via the shared
            # per-sweep helper (one FE solve + one shuffle-join + local
            # solves per RE coordinate per sweep)
            per_coord = cfg["per_coordinate"]
            fe = per_coord.get("fixed")
            if fe is None:
                return None
            sweeps = max(1, cfg["measured_sweeps"])
            fe_k = (
                float(cfg.get("fe_nnz") or cfg["fe_dim"])
                if cfg.get("fe_layout") == "sparse_ell"
                else float(cfg["fe_dim"])
            )
            re_specs = []
            passes = fe["examples"]
            for cid, info in cfg["coordinates"].items():
                pc = per_coord.get(cid)
                if pc is None:
                    continue
                active = cfg["re_state"][cid]["active_samples"]
                mean_evals_per_sweep = pc["examples"] / max(1, active) / sweeps
                re_specs.append(
                    (
                        active,
                        float(info["d_re"]),
                        mean_evals_per_sweep,
                        12.0 * info["d_re"],  # (idx, value) pairs per row
                    )
                )
                passes += pc["examples"]
            total = sweeps * spark_cost_model.game_sweep_seconds(
                (cfg["n"], fe_k, cfg["fe_dim"], fe["n_evals"] / sweeps),
                re_specs,
            )
            if total <= 0:
                return None
            rate = passes / total / spark_cost_model.DEFAULT_CLUSTER.executors
        else:
            return None
    except (KeyError, TypeError, ZeroDivisionError) as e:
        _log(f"[bench] spark model skipped for {name}: {type(e).__name__} {e}")
        return None
    return {
        "modeled_examples_per_sec_per_executor": round(rate, 1),
        "cluster": f"{spark_cost_model.DEFAULT_CLUSTER.executors}x"
        f"{spark_cost_model.DEFAULT_CLUSTER.cores_per_executor} cores",
    }

# Per-chip peak matmul FLOP/s by device kind, for the dtype noted.
# Sources: public TPU spec sheets (cloud.google.com/tpu/docs/system-architecture).
_PEAK_FLOPS = {
    "v6": (918e12, "bf16"),
    "v5p": (459e12, "bf16"),
    "v5e": (197e12, "bf16"),
    "v5 lite": (197e12, "bf16"),
    "v4": (275e12, "bf16"),
    "v3": (123e12, "bf16"),
    "v2": (45e12, "bf16"),
}

#: BENCH_SMOKE=1 shrinks every config to seconds-scale shapes — used to
#: validate the harness end-to-end on CPU (and in CI) without TPU time.
SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"

#: Versioning of what ``examples_per_sec`` COUNTS, so cross-round trends
#: stay interpretable (VERDICT r5 weak #3):
#:   1 (r4)  — GAME RE examples counted padded block rows the solver
#:             touched (passive + padding lanes inflated the number);
#:   2 (r5)  — active rows only (the honest work unit; reads ~18% lower
#:             than v1 at identical speed);
#:   3 (r6+) — still active-based, but rows now ALSO carry the touched
#:             count (``examples_per_sec_touched``, the v1-comparable
#:             series) plus the compile-bill split.
#:   4 (r9+) — throughput unchanged from v3; GAME rows additionally
#:             carry the device-memory ledger columns (``mem.peak_bytes``
#:             live high-watermark, ``mem.exec_temp_bytes`` XLA scratch
#:             across the AOT executables, H2D/D2H bytes) — capacity
#:             claims become measured columns, gated by QUALITY_BANDS.
METRIC_VERSION = 5

#: Per-config quality bands (VERDICT r5 next #6): a config that produces
#: a throughput number while its MODEL is garbage must FAIL, not publish.
#: gnorm bands apply only when the solve converged by value/gradient
#: (ConvergenceReason 2/3) — a max-iteration stop at reduced CPU scale is
#: slow, not wrong. Bands are generous multiples of measured-healthy
#: values (BENCH_r05: a1a 0.039, tron 1.83 at n=2^16, GAME AUC 0.993) so
#: draw noise never trips them; a poisoned/unoptimized solve exceeds
#: them by orders of magnitude (tests/test_bench_quality.py).
QUALITY_BANDS = {
    "a1a_logistic_lbfgs": {"gnorm_max": 1.0},
    "linear_tron": {"gnorm_max": 100.0},
    "sparse_poisson_owlqn": {"gnorm_max": 5000.0},
    # require_memory: a GAME row without its device-memory ledger
    # columns (mem.peak_bytes high-watermark > 0, mem.exec_temp_bytes
    # present) is a capacity claim with no accounting behind it — the
    # ledger broke or was disabled, and the row must fail, not publish
    "glmix_game_estimator": {
        "grouped_auc_min": {"smoke": 0.55, "cpu": 0.8, "tpu": 0.8},
        "require_memory": True,
        # feature-cache ingest A/B (ROADMAP 4): a cached replay that is
        # not wire-identical to the avro read is garbage, not a speedup
        "cache_parity_max": 1e-6,
        "cache_warm_decode_spans_max": 0,
        # meshed 1-vs-8 scaling A/B (ROADMAP 1): the 8-device fit must
        # reproduce the single-device coefficients (f64, per-entity
        # keyed), run ZERO steady-state retraces, pass its own SPMD
        # program audit, and actually SHARD the entity tables — the
        # per-device footprint ratio has padding slop at smoke scale
        # (buckets pad the entity axis to divide 8), so the floor is 3,
        # not 8; measured 5.3 at n=2048
        "mesh_parity_max": 1e-9,
        "mesh_steady_compiles_max": 0,
        "mesh_audit_findings_max": 0,
        "mesh_table_shard_ratio_min": 3.0,
        # fleet leg (ISSUE 14): a healthy 2-process Gloo meshed fit must
        # not flag any straggler — per-sweep barrier-arrival skew above
        # the threshold means one worker is dragging the collective, the
        # regression every later mesh-perf PR must not introduce. The
        # ratio band is the straggler threshold itself (metric_version 5
        # rows carry mesh.fleet.* + the device-time breakdown fields)
        "fleet_max_skew_ratio_max": 2.0,
        "fleet_stragglers_max": 0,
    },
    "game_ctr_scale": {
        "grouped_auc_min": {"smoke": 0.55, "cpu": 0.8, "tpu": 0.8},
        "require_memory": True,
    },
    # the streaming scorer must be BIT-PARITY (f32 accumulation tolerance)
    # with the monolithic host path, and its steady state must dispatch
    # precompiled programs only — a throughput number from a divergent or
    # retracing scorer must fail, not publish
    "game_scoring_stream": {
        "score_parity_rel_max": 1e-3,
        "steady_compiles_max": 0,
        # the warm mmap replay must be float-identical to the avro-fed
        # stream (same fused engine, same batch shapes) and must run ZERO
        # avro-decode spans — the cache's whole claim, obs-pinned
        "cache_parity_max": 1e-6,
        "cache_warm_decode_spans_max": 0,
    },
    # the Poisson tail-latency config (ROADMAP 2 / ISSUE 15): the
    # SUSTAINED leg (0.5× measured capacity) must hold its p99 under a
    # generous wall band (5 s = "not wedged", far above any healthy
    # batch on even a loaded 2-core builder) AND pass its own armed SLO
    # gate — a throughput row whose tail blew the objective, or whose
    # violation census flags a dominant stage, must fail, not publish
    "game_scoring_tail": {
        "tail_p99_s_max": 5.0,
        "tail_slo_ok": True,
        # arming the causal trace plane at sample_n=1 (every request
        # recorded — worst-case record volume) may not move the paced
        # leg's p99 by more than 100% of the disarmed p99. Deliberately
        # loose: p99 on a loaded 2-core builder is noisy and the gate is
        # "recording is cheap relative to the leg", not a microbenchmark
        # hero number — scripts/measure_obs_overhead.py is where tight
        # overhead experiments run
        "trace_overhead_p99_frac_max": 1.0,
    },
    # the hot-swap config's whole claim is "zero downtime": a swap that
    # failed or dropped even one request, or whose post-flip answers
    # diverge from a cold scorer on the new model, must fail, not publish
    "game_serving_swap": {
        "serve_swap_failed_requests_max": 0,
        "serve_swap_parity_max": 1e-6,
    },
    # the daily retrain config (ISSUE 17): the warm delta day must be
    # >= 3x faster than the cold streaming fit (steady sweep walls —
    # both sides compile-free by the zero-steady-compile gate below),
    # the double buffer must actually overlap H2D with compute (>= 50%
    # of H2D wall spent under an in-flight program), and the warm start
    # must not perturb a single untouched entity
    "glmix_daily_retrain": {
        "warm_speedup_min": 3.0,
        "h2d_overlap_frac_min": 0.5,
        "stream_steady_compiles_max": 0,
        "warm_carryover_exact": True,
    },
}

#: ConvergenceReason codes that mean "the tolerance check stopped us"
_CONVERGED_REASONS = (2, 3)  # FUNCTION_VALUES / GRADIENT converged


def check_quality_bands(name: str, detail: dict) -> list[str]:
    """Violations of ``QUALITY_BANDS`` for one finished config row (empty
    list = healthy). The orchestrator fails the config on any violation —
    a throughput number from a garbage model is worse than no number."""
    import math

    band = QUALITY_BANDS.get(name)
    if not band:
        return []
    out = []
    gnorm_max = band.get("gnorm_max")
    if (
        gnorm_max is not None
        and detail.get("converged_reason") in _CONVERGED_REASONS
    ):
        g = detail.get("gnorm_final")
        if g is not None and (not math.isfinite(g) or g > gnorm_max):
            out.append(
                f"gnorm_final {g:.4g} > {gnorm_max} for a "
                "tolerance-converged solve"
            )
    parity_max = band.get("score_parity_rel_max")
    if parity_max is not None:
        rel = (detail.get("parity") or {}).get("max_rel_diff")
        if rel is None or not math.isfinite(rel) or rel > parity_max:
            out.append(
                f"streaming-vs-monolithic score parity {rel} > {parity_max}"
            )
    steady_max = band.get("steady_compiles_max")
    if steady_max is not None:
        sc = detail.get("steady_compiles")
        if sc is None or sc > steady_max:
            out.append(
                f"steady-state scoring compiled {sc} programs "
                f"(> {steady_max}; retrace leaked into the hot loop)"
            )
    cache_parity_max = band.get("cache_parity_max")
    if cache_parity_max is not None:
        cache = detail.get("cache") or {}
        par = cache.get("parity_max_abs")
        if par is None or not math.isfinite(par) or par > cache_parity_max:
            out.append(
                f"feature-cache wire parity {par} > {cache_parity_max} "
                "(the cached replay differs from the avro read)"
            )
    decode_spans_max = band.get("cache_warm_decode_spans_max")
    if decode_spans_max is not None:
        wd = (detail.get("cache") or {}).get("warm_decode_spans")
        if wd is None or wd > decode_spans_max:
            out.append(
                f"warm cache run emitted {wd} io.decode span(s) "
                f"(> {decode_spans_max}; avro decode leaked into the "
                "warm path)"
            )
    mesh_parity_max = band.get("mesh_parity_max")
    if mesh_parity_max is not None:
        mesh = detail.get("mesh") or {}
        if mesh.get("error"):
            out.append(f"mesh scaling A/B failed: {mesh['error'][:300]}")
        else:
            par = mesh.get("parity_max_abs")
            if par is None or not math.isfinite(par) or par > mesh_parity_max:
                out.append(
                    f"meshed-vs-single-device coefficient parity {par} > "
                    f"{mesh_parity_max}"
                )
            sc = mesh.get("steady_compiles")
            sc_max = band.get("mesh_steady_compiles_max", 0)
            if sc is None or sc > sc_max:
                out.append(
                    f"meshed fit compiled {sc} programs in steady state "
                    f"(> {sc_max}; retrace leaked into the on-mesh loop)"
                )
            af = mesh.get("audit_findings")
            af_max = band.get("mesh_audit_findings_max", 0)
            if af is None or af > af_max:
                out.append(
                    f"SPMD program audit over the meshed fit's own "
                    f"executables reported {af} finding(s) (> {af_max})"
                )
            ratio_min = band.get("mesh_table_shard_ratio_min")
            ratio = mesh.get("table_shard_ratio")
            if ratio_min is not None and (
                ratio is None or not math.isfinite(ratio) or ratio < ratio_min
            ):
                out.append(
                    f"entity-table per-device footprint ratio {ratio} < "
                    f"{ratio_min} — the meshed tables are not actually "
                    "sharded"
                )
            skew_max = band.get("fleet_max_skew_ratio_max")
            # presence-gated: rows from before the fleet leg existed
            # (metric_version <= 4 history, legacy fixtures) carry no
            # "fleet" section and must keep passing; any row that RAN
            # the leg — including a failed one — is fully gated
            if skew_max is not None and "fleet" in mesh:
                fleet = mesh.get("fleet") or {}
                if fleet.get("error"):
                    out.append(
                        f"fleet leg failed: {fleet['error'][:300]}"
                    )
                else:
                    sk = fleet.get("max_skew_ratio")
                    if sk is None or not math.isfinite(sk) or sk > skew_max:
                        out.append(
                            f"fleet per-sweep skew ratio {sk} > {skew_max} "
                            "— one worker is dragging the meshed sweep "
                            "(straggler regression)"
                        )
                    strag_max = band.get("fleet_stragglers_max", 0)
                    n_strag = len(fleet.get("stragglers") or [])
                    if n_strag > strag_max:
                        out.append(
                            f"fleet leg flagged {n_strag} straggler(s) "
                            f"(> {strag_max}) in a healthy run"
                        )
    tail_p99_max = band.get("tail_p99_s_max")
    if tail_p99_max is not None:
        tail = detail.get("tail") or {}
        p99 = tail.get("p99_s")
        if p99 is None or not math.isfinite(p99) or p99 > tail_p99_max:
            out.append(
                f"sustained-leg p99 end-to-end latency {p99} s > "
                f"{tail_p99_max} s (queueing included — the tail the "
                "SLO plane exists to see)"
            )
    if band.get("tail_slo_ok"):
        tail = detail.get("tail") or {}
        if not tail.get("gate_ok"):
            out.append(
                "sustained leg breached its armed SLO: "
                f"{'; '.join(tail.get('slo_violations') or ['no gate data'])}"
            )
    trace_frac_max = band.get("trace_overhead_p99_frac_max")
    # presence-gated: rows from before the trace-overhead A/B existed
    # (metric_version history, legacy fixtures) carry no "trace_overhead"
    # section and must keep passing; any row that RAN the A/B — including
    # one whose armed leg detonated — is fully gated
    if trace_frac_max is not None and "trace_overhead" in detail:
        to = detail.get("trace_overhead") or {}
        frac = to.get("p99_delta_frac")
        if frac is None or not math.isfinite(frac) or frac > trace_frac_max:
            out.append(
                f"arming the causal trace plane moved the paced leg's p99 "
                f"by {frac} of the disarmed p99 (> {trace_frac_max}; "
                "recording is not cheap relative to the leg)"
            )
    swap_failed_max = band.get("serve_swap_failed_requests_max")
    if swap_failed_max is not None:
        failed = detail.get("failed_requests")
        shed = detail.get("shed")
        if failed is None or failed > swap_failed_max:
            out.append(
                f"hot swap under load failed/misanswered {failed} "
                f"request(s) (> {swap_failed_max}; zero-downtime claim "
                "broken)"
            )
        if shed is None or shed > swap_failed_max:
            out.append(
                f"hot swap under load shed {shed} request(s) "
                f"(> {swap_failed_max}) at sustained sub-capacity traffic"
            )
        if not detail.get("swap"):
            out.append("serving-swap row carries no swap record at all")
    swap_parity_max = band.get("serve_swap_parity_max")
    if swap_parity_max is not None:
        par = detail.get("post_swap_parity_max_abs")
        if par is None or not math.isfinite(par) or par > swap_parity_max:
            out.append(
                f"post-swap score parity {par} > {swap_parity_max} vs a "
                "cold scorer on the new model"
            )
        if not detail.get("post_flip_requests"):
            out.append(
                "no post-flip requests were answered — the parity gate "
                "measured nothing"
            )
    speedup_min = band.get("warm_speedup_min")
    if speedup_min is not None:
        sp = (detail.get("retrain") or {}).get("warm_speedup")
        if sp is None or not math.isfinite(sp) or sp < speedup_min:
            out.append(
                f"warm-start retrain speedup {sp} < {speedup_min}x vs the "
                "cold streaming fit (steady sweep walls)"
            )
    overlap_min = band.get("h2d_overlap_frac_min")
    if overlap_min is not None:
        ov = (detail.get("stream") or {}).get("h2d_overlap_fraction")
        if ov is None or not math.isfinite(ov) or ov < overlap_min:
            out.append(
                f"H2D overlap fraction {ov} < {overlap_min} — the double "
                "buffer is not overlapping host-to-device copies with "
                "chunk compute"
            )
    stream_sc_max = band.get("stream_steady_compiles_max")
    if stream_sc_max is not None:
        sc = detail.get("stream_steady_compiles")
        if sc is None or sc > stream_sc_max:
            out.append(
                f"streaming fit compiled {sc} program(s) in steady state "
                f"(> {stream_sc_max}; retrace leaked into the chunk loop)"
            )
    if band.get("warm_carryover_exact"):
        ro = detail.get("retrain") or {}
        if not ro.get("carryover_bit_exact"):
            out.append(
                "warm-start retrain perturbed untouched entities "
                "(carryover not bit-exact)"
            )
        if not ro.get("touched_entities"):
            out.append(
                "delta-day retrain touched no entities — the warm leg "
                "measured nothing"
            )
    if band.get("require_memory"):
        mem = detail.get("mem") or {}
        peak = mem.get("peak_bytes")
        if peak is None or not math.isfinite(peak) or peak <= 0:
            out.append(
                f"mem.peak_bytes {peak!r} absent or non-positive — the "
                "device-memory ledger produced no live-census data for a "
                "GAME config"
            )
        if mem.get("exec_temp_bytes") is None:
            out.append(
                "mem.exec_temp_bytes absent — no AOT executable reported "
                "a static footprint"
            )
    auc_min = band.get("grouped_auc_min")
    if auc_min is not None:
        if isinstance(auc_min, dict):
            auc_min = auc_min.get(
                detail.get("scale", "cpu"), min(auc_min.values())
            )
        auc = (detail.get("grouped_auc") or {}).get("value")
        if auc is None or not math.isfinite(auc) or auc < auc_min:
            out.append(f"grouped_auc {auc} < {auc_min}")
    return out


def _pick(scale, smoke, cpu, tpu):
    """Backend-aware shape selection. TPU gets the full BASELINE shapes;
    the CPU fallback gets shapes a CPU finishes inside the per-config
    timeout (every output records its n/d/… fields, so a CPU-scale number
    can never masquerade as the TPU one)."""
    return {"smoke": smoke, "cpu": cpu, "tpu": tpu}[scale]

#: config name → (worker timeout seconds, attempts)
CONFIG_PLAN = [
    ("a1a_logistic_lbfgs", 600, 3),
    ("linear_tron", 900, 3),
    ("sparse_poisson_owlqn", 2400, 2),
    # the GAME configs compile tens of programs (per-bucket RE solves);
    # compiles are slow, so their budgets cover a cold cache — retries
    # resume from the persistent compile cache
    ("glmix_game_estimator", 2400, 2),
    # CTR scale compiles ~30 programs (per-bucket RE solves x 2
    # coordinates); a COLD cache spent the whole former 3600 s budget in
    # remote compiles alone (r4 attempt 2) — the retry then finishes fast
    # from the persistent cache, but the first attempt needs the headroom
    ("game_ctr_scale", 5400, 2),
    # streaming inference A/B: decode → fused device scoring → sharded
    # write, vs the monolithic materialize-everything path on the same
    # files; compiles one program per batch shape (cheap, AOT)
    ("game_scoring_stream", 900, 2),
    # open-loop Poisson tail latency over the streaming scorer
    # (scripts/load_harness.py in-process): capacity calibration, then
    # paced legs reporting p50/p90/p99/p99.9 end-to-end with queueing
    # included, gated by the armed SLO
    ("game_scoring_tail", 900, 2),
    # serving hot swap under load (ISSUE 16): paced traffic through the
    # always-on engine, one mid-run zero-downtime model swap; in-process,
    # AOT shapes only, so the budget is mostly the two model builds
    ("game_serving_swap", 900, 2),
    # the daily warm-start retrain scenario (ISSUE 17): a cold streaming
    # fit (double-buffered chunk pipeline) + a 1/8-size warm delta day —
    # two fits, few programs (chunk shapes repeat), so the budget covers
    # a cold compile cache with room to spare
    ("glmix_daily_retrain", 1800, 2),
]

#: BENCH_PARTIAL_PATH redirects the cumulative artifact — a CPU-pinned
#: builder run must not race the TPU rerun loop's BENCH_partial.json
PARTIAL_PATH = os.environ.get("BENCH_PARTIAL_PATH") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_partial.json"
)


def launch_config_worker(name: str, timeout_s: float, env=None):
    """Run one config in a killable worker subprocess and parse its
    BENCHCFG_JSON marker (shared with scripts/rerun_bench_configs.py).
    Returns ``(detail, None)`` on success, ``(None, error_string)``
    otherwise; the worker's stderr is passed through either way."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--config", name],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout >{timeout_s}s (killed)"
    sys.stderr.write(out.stderr or "")
    sys.stderr.flush()
    marker = [
        ln
        for ln in (out.stdout or "").splitlines()
        if ln.startswith("BENCHCFG_JSON: ")
    ]
    if out.returncode == 0 and marker:
        return json.loads(marker[-1][len("BENCHCFG_JSON: "):])["detail"], None
    return None, (
        f"rc={out.returncode}; "
        f"{(out.stderr or '').strip().splitlines()[-3:]}"
    )


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# TPU probe (killable subprocess — backend init can hang, not just fail)
# ---------------------------------------------------------------------------

_PROBE_SRC = (
    "import jax, jax.numpy as jnp\n"
    "d = jax.devices()\n"
    # float() read-back, not block_until_ready: a backend that reports
    # ready at enqueue would pass the probe on a hung chip
    "s = float(jnp.sum(jnp.ones((128, 128)) @ jnp.ones((128, 128))))\n"
    "assert s == 128.0 * 128 * 128, s\n"
    "print('PROBE_OK', d[0].platform, d[0].device_kind, flush=True)\n"
)


def _probe_tpu(attempts: int = 3, timeout_s: float = 180.0):
    """Probe TPU availability in a killable subprocess. Returns the device
    kind string on success, None on failure."""
    for attempt in range(attempts):
        t0 = time.perf_counter()
        try:
            out = subprocess.run(
                [sys.executable, "-c", _PROBE_SRC],
                capture_output=True,
                text=True,
                timeout=timeout_s,
            )
            took = time.perf_counter() - t0
            if out.returncode == 0 and "PROBE_OK" in out.stdout:
                line = out.stdout.strip().splitlines()[-1]
                parts = line.split(" ", 2)
                if len(parts) == 3 and parts[1] == "tpu":
                    _log(f"[bench] TPU probe ok in {took:.0f}s: {line}")
                    return parts[2]
                _log(
                    f"[bench] probe reached a non-TPU backend ({line}); "
                    "treating as TPU-unreachable"
                )
            _log(
                f"[bench] TPU probe attempt {attempt + 1}/{attempts} failed "
                f"(rc={out.returncode}, {took:.0f}s): "
                f"{(out.stderr or '').strip().splitlines()[-1:] or 'no stderr'}"
            )
        except subprocess.TimeoutExpired:
            _log(
                f"[bench] TPU probe attempt {attempt + 1}/{attempts} HUNG "
                f">{timeout_s:.0f}s; killed"
            )
        wait = min(10 * 2**attempt, 60)
        if attempt + 1 < attempts:
            _log(f"[bench] retrying probe in {wait}s")
            time.sleep(wait)
    return None


# ---------------------------------------------------------------------------
# Worker-side helpers
# ---------------------------------------------------------------------------


def _init_backend():
    """Initialize JAX in THIS process; ``JAX_PLATFORMS`` alone decides
    the platform."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    if devs[0].platform == "tpu":
        # persistent compile cache makes per-config TPU retries cheap
        # (skipped on CPU: XLA:CPU AOT caching is machine-feature
        # sensitive and warns/SIGILLs across differing hosts)
        from photon_tpu.util.compile_cache import enable_persistent_cache

        enable_persistent_cache()
    # read-back, not block_until_ready: proves the backend actually executes
    float(jnp.sum(jnp.ones((8, 8)) @ jnp.ones((8, 8))))
    return devs[0].platform, devs[0].device_kind


def _peak_for(device_kind: str, platform: str):
    if platform != "tpu" and "tpu" not in device_kind.lower():
        return None, None
    kind = device_kind.lower()
    for key, (peak, dtype) in _PEAK_FLOPS.items():
        if key in kind:
            return peak, dtype
    raise ValueError(
        f"no peak FLOP/s on record for TPU kind {device_kind!r}: add it to "
        "_PEAK_FLOPS with its source — a utilization against a guessed "
        "peak is not a measurement"
    )


def _digest_wrap(fn):
    """Wrap a pytree-returning function so the jitted wrapper ALSO returns
    an in-program scalar with a data dependence on every leaf; timing
    ``float(digest)`` then bounds the REAL device execution with a single
    round trip. A guard against backends whose ``block_until_ready``
    returns at enqueue (util/force.py); whether a local chip still needs
    it is an open question (PERF.md, Open questions)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def wrapped(*args):
        out = fn(*args)
        dig = jnp.float32(0)
        for leaf in jax.tree_util.tree_leaves(out):
            if hasattr(leaf, "dtype") and getattr(leaf, "size", 0):
                dig = dig + jnp.asarray(leaf).reshape(-1)[0].astype(
                    jnp.float32
                )
        return out, dig

    return wrapped


def _timed_run(fn, key):
    """Compile+warm on one PRNG key, then measure a fresh run on a DIFFERENT
    key. The inputs MUST differ between the warm and timed calls: a backend
    that replays identical (executable, inputs) executions from a cache
    would otherwise hand back the warm call's result as the timed one.

    BENCH_PROFILE=<dir> wraps the timed run in a jax.profiler trace
    (VERDICT r2 weak #3: perf claims need profile evidence, not just wall
    clocks).

    The key is folded with fresh wall-clock entropy first, so that not
    even a replay cache that persists across sessions can answer from a
    previous round's identical program.

    The wall is closed by fetching the digest scalar (``_digest_wrap``),
    never by block_until_ready (see ``_digest_wrap``).

    Returns ``(result, wall, entropy)`` — the folded time_ns value is
    surfaced so each config's JSON row can record it (``value_entropy``):
    convergence-dependent metrics (n_evals, wall_to_converge_s) vary with
    the data draw, and cross-round deltas need to separate that draw noise
    from real regressions (ADVICE r5 #4)."""
    import contextlib

    import jax

    entropy = time.time_ns() & 0x7FFFFFFF
    key = jax.random.fold_in(key, entropy)
    k_warm, k_timed = jax.random.split(key)
    forced = _digest_wrap(fn)
    float(forced(k_warm)[1])
    prof_dir = os.environ.get("BENCH_PROFILE", "").strip()
    ctx = (
        jax.profiler.trace(prof_dir)
        if prof_dir
        else contextlib.nullcontext()
    )
    with ctx:
        t0 = time.perf_counter()
        out, dig = forced(k_timed)
        float(dig)
        wall = time.perf_counter() - t0
    return out, wall, entropy


# ---------------------------------------------------------------------------
# Config 1 — a1a-shaped logistic L-BFGS+L2 (BASELINE.md config 1).
# a1a: 1,605 train samples, 123 binary features (+intercept), ~14 active
# features/sample. Zero-egress environment → synthesize the same
# shape/sparsity; 124 floats/row is trivially dense territory on a TPU tile.
# ---------------------------------------------------------------------------


def config_a1a(peak_flops, scale):
    del scale  # a1a is tiny on every backend
    import jax
    import jax.numpy as jnp

    from photon_tpu.ops.losses import LogisticLoss, sigmoid
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optimize import OptimizerConfig, minimize_lbfgs
    from photon_tpu.types import LabeledBatch

    dtype = jnp.float32
    n, d = 1605, 124
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-7)

    @jax.jit
    def run(key):
        k1, k2, k3 = jax.random.split(key, 3)
        active = (jax.random.uniform(k1, (n, d)) < 14.0 / d).astype(dtype)
        x = active.at[:, 0].set(1.0)  # intercept column
        w_true = jax.random.normal(k2, (d,), dtype) * 0.5
        labels = (jax.random.uniform(k3, (n,)) < sigmoid(x @ w_true)).astype(
            dtype
        )
        batch = LabeledBatch(
            features=x,
            labels=labels,
            offsets=jnp.zeros((n,), dtype),
            weights=jnp.ones((n,), dtype),
        )
        return minimize_lbfgs(
            None,
            jnp.zeros((d,), dtype),
            cfg,
            oracle=obj.directional_oracle(batch),  # production default path
        )

    res, wall, entropy = _timed_run(run, jax.random.PRNGKey(1))
    evals = int(res.n_evals)
    # margin-space line search: trials are O(N) elementwise; feature-block
    # passes are the honest FLOP unit (2·N·D flops per pass)
    passes = int(res.n_feature_passes) or 2 * evals
    flops = 2.0 * n * d * passes
    return {
        "n": n,
        "d": d,
        "value_entropy": entropy,
        "wall_to_converge_s": round(wall, 4),
        "iterations": int(res.iterations),
        "n_evals": evals,
        "n_feature_passes": passes,
        "converged_reason": int(res.reason),
        "gnorm_final": float(jnp.linalg.norm(res.gradient)),
        "examples_per_sec": round(n * evals / wall, 1),
        "analytic_flops": flops,
        "mfu": round(flops / wall / peak_flops, 6) if peak_flops else None,
        # ~1605×124 is microseconds of compute against a dispatch round
        # trip — the wall measures the launch, not the framework. Keep as
        # a smoke/parity row only.
        "floor_bound": True,
        "note": "wall ≈ per-dispatch round-trip floor; smoke row, "
        "not perf evidence",
    }


# ---------------------------------------------------------------------------
# Config 2 — linear regression, TRON (Hessian-vector-product path).
# Sized so the matmuls can dominate: 2^19 x 2048 (the r2 shape of 131k x
# 1024 spent ~5e8 flops/eval ≈ microseconds of MXU time against a fixed
# while-loop latency floor — MFU was latency, not compute; VERDICT r2
# weak #3). The [N, D] block is 4 GB f32 / 2 GB bf16.
# ---------------------------------------------------------------------------


def config_tron(peak_flops, scale):
    import jax
    import jax.numpy as jnp

    from photon_tpu.ops.losses import SquaredLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optimize import OptimizerConfig, minimize_tron
    from photon_tpu.types import LabeledBatch

    dtype = jnp.float32
    n, d = _pick(
        scale, (1 << 12, 256), (1 << 16, 1024), (1 << 19, 2048)
    )
    obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0)
    cfg = OptimizerConfig().tron_defaults()

    def make_run(feat_dtype):
        @jax.jit
        def run(key):
            k1, k2, k3 = jax.random.split(key, 3)
            x = jax.random.normal(k1, (n, d), dtype)
            w_true = jax.random.normal(k2, (d,), dtype) * 0.1
            labels = x @ w_true + 0.1 * jax.random.normal(k3, (n,), dtype)
            batch = LabeledBatch(
                features=x.astype(feat_dtype),
                labels=labels,
                offsets=jnp.zeros((n,), dtype),
                weights=jnp.ones((n,), dtype),
            )
            return minimize_tron(
                lambda w: obj.value_and_gradient(w, batch),
                None,
                jnp.zeros((d,), dtype),
                cfg,
                hvp_factory=lambda w: obj.hessian_operator(w, batch),
            )

        return run

    def summarize(res, wall, feat_bytes):
        evals, hvp = int(res.n_evals), int(res.n_hvp)
        # exact feature-block passes (incl. the once-per-outer-iteration
        # curvature pass the hvp_factory hoists out of the CG loop)
        passes = int(res.n_feature_passes) or 2 * (evals + hvp)
        flops = 2.0 * n * d * passes
        # GLMs are memory-bound: report achieved HBM traffic too (one
        # [N, D] read per pass).
        approx_bytes = feat_bytes * n * d * passes
        return {
            "wall_to_converge_s": round(wall, 4),
            "iterations": int(res.iterations),
            "n_evals": evals,
            "n_hvp": hvp,
            "n_feature_passes": passes,
            "converged_reason": int(res.reason),
            "gnorm_final": float(jnp.linalg.norm(res.gradient)),
            "examples_per_sec": round(n * (evals + hvp) / wall, 1),
            "analytic_flops": flops,
            "mfu": round(flops / wall / peak_flops, 6)
            if peak_flops
            else None,
            "achieved_gbps": round(approx_bytes / wall / 1e9, 1),
        }

    res, wall, entropy = _timed_run(make_run(dtype), jax.random.PRNGKey(2))
    out = {"n": n, "d": d, "value_entropy": entropy, **summarize(res, wall, 4.0)}

    # bfloat16 feature block (f32 MXU accumulation, f32 optimizer state):
    # halves HBM traffic on the dominant [N, D] reads (VERDICT r2 weak #3).
    # Skipped on the CPU fallback — XLA:CPU emulates bf16 and the number
    # would measure the emulation, not the feature.
    if scale != "cpu":
        res_b, wall_b, entropy_b = _timed_run(
            make_run(jnp.bfloat16), jax.random.PRNGKey(2)
        )
        out["bf16"] = summarize(res_b, wall_b, 2.0)
        out["bf16"]["value_entropy"] = entropy_b
        out["bf16"]["final_loss_rel_diff"] = round(
            abs(float(res_b.value) - float(res.value))
            / max(abs(float(res.value)), 1e-12),
            6,
        )
    return out


# ---------------------------------------------------------------------------
# Config 3 — Poisson elastic-net OWLQN on a sparse-ELL shard (BASELINE.md
# config 3): n=2^20 samples, d=2^20 features, 56 slots/row. The dense block
# would be 4 TB; the ELL batch is ~0.45 GB (VERDICT r2 missing #1).
# ---------------------------------------------------------------------------


def config_sparse_poisson(peak_flops, scale):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.ops.losses import PoissonLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.ops.sparse_windows import maybe_build_windows
    from photon_tpu.optimize import OptimizerConfig, minimize_owlqn
    from photon_tpu.types import SparseBatch

    dtype = jnp.float32
    n, d, k = _pick(
        scale,
        (1 << 13, 1 << 13, 16),
        (1 << 17, 1 << 17, 56),
        (1 << 20, 1 << 20, 56),
    )
    l1, l2 = 0.5e-3, 0.5e-3  # elastic net α=0.5, λ=1e-3
    obj = GLMObjective(loss=PoissonLoss, l2_weight=l2, l1_weight=l1)
    cfg = OptimizerConfig(
        max_iterations=_pick(scale, 30, 50, 100), tolerance=1e-7
    )

    # Data is generated on HOST here (unlike configs 1-2): the column-window
    # layout that reroutes the backward scatter around XLA:TPU's serialized
    # scatter lowering (ops/sparse_windows.py) needs a host-side sort of the
    # static indices anyway. The one-time upload is reported separately and
    # never inside the timed region.
    t0 = time.perf_counter()
    rng = np.random.default_rng(3)
    idx = rng.integers(1, d, size=(n, k)).astype(np.int32)
    idx[:, 0] = 0  # intercept column — one hot column tests instance spill
    vals = (rng.standard_normal((n, k)) / np.sqrt(k)).astype(np.float32)
    vals[:, 0] = 1.0
    w_true = (rng.standard_normal(d) * 0.3).astype(np.float32)
    margin = np.sum(vals * w_true[idx], axis=-1)
    rate = np.exp(np.clip(margin - 0.5, -4.0, 3.0))
    labels = rng.poisson(rate).astype(np.float32)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    windows = maybe_build_windows(idx, vals, d)
    win_build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    batch = SparseBatch(
        indices=jnp.asarray(idx),
        values=jnp.asarray(vals),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros((n,), dtype),
        weights=jnp.ones((n,), dtype),
        windows=windows,
    )
    from photon_tpu.util.force import force

    force(batch)  # read-back barrier: enqueue-async device_put otherwise
    upload_s = time.perf_counter() - t0
    win_stats = None
    if windows is not None:
        w_inst, length = windows.rows.shape
        win_stats = {
            "instances": int(w_inst),
            "instance_len": int(length),
            "window": int(windows.window),
            "padding_waste": round(1.0 - n * k / (w_inst * length), 4),
            "impl": "prefix",
        }
    _log(
        f"[bench] config3 host gen {gen_s:.1f}s window build "
        f"{win_build_s:.1f}s upload {upload_s:.1f}s windows={win_stats}"
    )

    def make_run(run_cfg):
        @jax.jit
        def run(batch, w0):
            return minimize_owlqn(
                None,
                w0,
                l1,
                run_cfg,
                oracle=obj.smooth_margin_oracle(batch),  # production path
            )

        return run

    # --- calibration gate --------------------------------------------------
    # A TPU program is not killable mid-execution: one optimizer while_loop
    # over this shape with a pathological inner op (e.g. the serialized
    # scatter the windowed layout exists to avoid) would occupy the REMOTE
    # chip for hours after the client timeout killed the worker — exactly
    # what wedged the chip this round. So: measure a 2-iteration solve on a
    # small row-slice first, project the full-run cost from its on-device
    # eval counters, and only launch the full program if the projection
    # fits comfortably inside the worker timeout.
    cal_gate = {"projected_full_s": None, "calibrated": False}
    if not SMOKE and n > (1 << 16):
        cal_n = 1 << 15
        cal_windows = maybe_build_windows(idx[:cal_n], vals[:cal_n], d)
        cal_batch = SparseBatch(
            indices=jnp.asarray(idx[:cal_n]),
            values=jnp.asarray(vals[:cal_n]),
            labels=jnp.asarray(labels[:cal_n]),
            offsets=jnp.zeros((cal_n,), dtype),
            weights=jnp.ones((cal_n,), dtype),
            windows=cal_windows,
        )
        cal_run = _digest_wrap(
            make_run(OptimizerConfig(max_iterations=2, tolerance=0.0))
        )
        float(cal_run(cal_batch, jnp.zeros((d,), dtype))[1])
        # entropy-fold: under a replay cache a fixed seed would hand back
        # last round's result and the gate would project from a fantasy
        # 0.0 s calibration
        cal_key = jax.random.fold_in(
            jax.random.PRNGKey(31), time.time_ns() & 0x7FFFFFFF
        )
        w0c = 1e-6 * jax.random.normal(cal_key, (d,), dtype)
        t0 = time.perf_counter()
        cal_res, cal_dig = cal_run(cal_batch, w0c)
        float(cal_dig)
        cal_wall = time.perf_counter() - t0
        cal_evals = max(int(cal_res.n_evals), 1)
        evals_per_iter = cal_evals / max(int(cal_res.iterations), 1)
        projected = (
            (cal_wall / cal_evals)
            * (n / cal_n)
            * evals_per_iter
            * cfg.max_iterations
        )
        cal_gate = {
            "calibrated": True,
            "cal_n": cal_n,
            "cal_wall_s": round(cal_wall, 3),
            "cal_evals": cal_evals,
            "projected_full_s": round(projected, 1),
        }
        _log(f"[bench] config3 calibration {cal_gate}")
        if projected > 900.0:
            _log(
                "[bench] config3 projected full-run cost exceeds the safe "
                "budget; reporting calibration-slice throughput instead of "
                "wedging the chip"
            )
            return {
                "n": cal_n,
                "d": d,
                "nnz_per_row": k,
                "scale_note": "reduced slice — full shape projected "
                f"{projected:.0f}s on this backend (gate at 900s)",
                "calibration": cal_gate,
                "wall_to_converge_s": round(cal_wall, 4),
                "iterations": int(cal_res.iterations),
                "n_evals": cal_evals,
                "examples_per_sec": round(cal_n * cal_evals / cal_wall, 1),
                "column_windows": win_stats,
            }

    # Full-scale solve. On TPU the whole solve can be many device-minutes;
    # one monolithic while_loop program is unkillable and can exceed the
    # transport's per-program execution limit (observed as `UNAVAILABLE:
    # TPU device error` mid-solve). SegmentedOWLQN re-dispatches the same
    # solve in bounded-iteration programs sized from the calibration so
    # each dispatch stays ~45 s.
    segment_iters = None
    if jax.default_backend() == "tpu" and cal_gate.get("calibrated"):
        per_iter_full = (
            (cal_gate["cal_wall_s"] / 2.0) * (n / float(cal_gate["cal_n"]))
        )
        segment_iters = max(1, min(50, int(45.0 / max(per_iter_full, 0.09))))
    if segment_iters is not None:
        from photon_tpu.optimize.owlqn import SegmentedOWLQN

        # batch flows through as a jit ARGUMENT (oracle built at trace
        # time) — a closed-over batch would bake ~0.5 GB of dense
        # constants into the remotely-compiled segment program
        solver = SegmentedOWLQN(
            None,
            l1,
            cfg,
            oracle_factory=obj.smooth_margin_oracle,
            segment_iters=segment_iters,
        )
        run = lambda b, w0: solver(w0, b)  # noqa: E731
        _log(f"[bench] config3 segmented dispatch: {segment_iters} it/seg")
    else:
        run = make_run(cfg)
    # warm on zeros, time from a different (≈identical-work) start point —
    # distinct inputs (entropy-folded key) defeat any replay of identical
    # executions. Walls close with a read-back (force), not
    # block_until_ready (util/force.py).
    # For the segmented path the final state depends on every segment
    # program, so forcing the last result bounds the whole chain.
    force(run(batch, jnp.zeros((d,), dtype)))
    w0_entropy = time.time_ns() & 0x7FFFFFFF
    w0_key = jax.random.fold_in(jax.random.PRNGKey(30), w0_entropy)
    w0 = 1e-6 * jax.random.normal(w0_key, (d,), dtype)
    t0 = time.perf_counter()
    res = run(batch, w0)
    force((res.x, res.n_evals, res.n_feature_passes))
    wall = time.perf_counter() - t0
    if segment_iters is not None:
        _log(f"[bench] config3 segments run: {solver.last_num_segments}")
    evals = int(res.n_evals)
    # value-only trials: one (idx, val) stream pass per trial + one
    # backward per iteration — exact from the pass counter
    passes = int(res.n_feature_passes) or 2 * evals
    nnz_flops = 2.0 * n * k * passes
    # USEFUL bytes: 8 B per nonzero (4 B index + 4 B value) per pass.
    # FETCHED bytes (ANALYTIC, from the gather's design, not a counter):
    # the chunked row gather reads a whole 128-lane row (128 × the
    # coefficient table's itemsize) per useful element — reporting both
    # makes the read amplification visible instead of burying it
    # (VERDICT r4 weak #1). A bf16 table halves the fetched stream.
    table_itemsize = jnp.dtype(dtype).itemsize
    approx_bytes = (4.0 + 4.0) * n * k * passes
    fetched_bytes = (128.0 * table_itemsize + 4.0) * n * k * passes
    w_final = res.x
    sparsity = float(jnp.mean((w_final == 0).astype(jnp.float32)))
    return {
        "n": n,
        "d": d,
        "nnz_per_row": k,
        "value_entropy": w0_entropy,
        "ell_batch_bytes": int(n * k * 8),
        "dense_equivalent_bytes": int(n) * int(d) * 4,
        "host_gen_s": round(gen_s, 1),
        "window_build_s": round(win_build_s, 1),
        "upload_s": round(upload_s, 1),
        "column_windows": win_stats,
        "calibration": cal_gate,
        "wall_to_converge_s": round(wall, 4),
        "iterations": int(res.iterations),
        "n_evals": evals,
        "n_feature_passes": passes,
        "converged_reason": int(res.reason),
        "gnorm_final": float(jnp.linalg.norm(res.gradient)),
        "examples_per_sec": round(n * evals / wall, 1),
        "analytic_flops": nnz_flops,
        "mfu": round(nnz_flops / wall / peak_flops, 6) if peak_flops else None,
        "achieved_gbps_useful": round(approx_bytes / wall / 1e9, 1),
        "achieved_gbps_fetched": round(fetched_bytes / wall / 1e9, 1),
        # analytic from the gather design (128-lane rows × table
        # itemsize), not a hardware counter
        "gather_read_amplification_analytic": round(
            fetched_bytes / approx_bytes, 1
        ),
        "coefficient_sparsity": round(sparsity, 4),
    }


# ---------------------------------------------------------------------------
# GAME helpers (configs 4 and 5): skewed synthetic CTR-ish data through the
# REAL framework path — GameData build → GameEstimator.fit → CD sweeps.
# ---------------------------------------------------------------------------


def _start_series_flusher(config_name: str):
    """Per-config time-resolved metric series (photon_tpu/obs/series):
    one ``<config>.series.jsonl`` trajectory under $PHOTON_OBS_DIR —
    the within-run throughput signal the terminal bench averages can't
    see (``scripts/bench_trend.py --series`` plots/gates it). Local
    instance, not the process-global flusher: bench runs configs back
    to back and each file must hold exactly one run."""
    from photon_tpu.obs.series import SeriesFlusher, flush_interval_s

    interval = flush_interval_s()
    if interval == 0:
        return None
    obs_dir = os.environ.get("PHOTON_OBS_DIR", "bench_obs")
    os.makedirs(obs_dir, exist_ok=True)
    path = os.path.join(obs_dir, f"{config_name}.series.jsonl")
    open(path, "w").close()  # one run per file, not append-across-runs
    return SeriesFlusher(path, interval).start()


def _stop_series_flusher(flusher) -> str | None:
    if flusher is None:
        return None
    flusher.stop()
    return flusher.path


def _zipf_ids(rng, n, num_entities, a=1.3):
    """Zipf-skewed entity sizes with guaranteed coverage: when the sample
    budget allows, every entity appears at least once (otherwise raw Zipf
    concentration models only a few % of the nominal entity count and the
    scale claim would be hollow); the remaining samples pile onto the
    skewed head."""
    import numpy as np

    ids = ((rng.zipf(a, size=n) - 1) % num_entities).astype(np.int64)
    if n >= num_entities:
        ids[:num_entities] = rng.permutation(num_entities)
    return ids


def _game_examples_from_tracker(tracker, datasets, n_real):
    """Real-sample × eval counts per coordinate from CD tracker infos.

    FE info is one OptimizeResult (n_evals scalar); RE info is a list of
    per-bucket OptimizeResult with n_evals[E]. Real (non-padding) rows per
    entity come from the host dataset buckets.

    Dual counting (METRIC_VERSION 3, VERDICT r5 weak #3): ``examples`` is
    the ACTIVE count (real data rows × evals — the honest work unit, the
    r5 metric), ``examples_touched`` is the padded-block count (bucket
    rows the vmapped solve actually processed × evals — the r4-comparable
    series). touched/active is the compute amplification padding costs.
    """
    import numpy as np

    per_coord: dict = {}
    for row in tracker:
        if "coordinate" not in row:
            continue
        cid, info = row["coordinate"], row["info"]
        entry = per_coord.setdefault(
            cid,
            {
                "examples": 0.0,
                "examples_touched": 0.0,
                "seconds": 0.0,
                "evals": 0,
            },
        )
        entry["seconds"] += row["seconds"]
        if isinstance(info, list):  # random effect: per-bucket results
            ds = datasets[cid]
            for bres, hb in zip(info, ds.buckets):
                ev = np.asarray(bres.n_evals, dtype=np.float64)
                rows_real = (np.asarray(hb.weights) > 0).sum(axis=1)
                e = len(rows_real)
                entry["examples"] += float((ev[:e] * rows_real).sum())
                # every lane of the padded [E, n_max] block runs the solve
                entry["examples_touched"] += float(
                    ev[:e].sum() * hb.labels.shape[1]
                )
                entry["evals"] += int(ev[:e].sum())
        else:  # fixed effect: dense batch, no padding rows off-mesh
            ev = int(info.n_evals)
            entry["examples"] += float(n_real) * ev
            entry["examples_touched"] += float(n_real) * ev
            entry["evals"] += ev
    return per_coord


def _pin_cache_env():
    """Pop ambient PHOTON_FEATURE_CACHE* env for the duration of a cache
    A/B (returns the saved dict to restore): the A/B passes its modes
    explicitly, and the knob convention is env-wins — an exported
    ``require`` would kill the cold leg against a fresh tempdir, an
    exported ``off`` would run both legs on avro and fail the
    warm-decode band with a misleading message (the same hazard
    scripts/check_obs_regression.py pins out of its canonical leg)."""
    return {
        k: os.environ.pop(k)
        for k in list(os.environ)
        if k.startswith("PHOTON_FEATURE_CACHE")
    }


def _cache_ingest_ab(data, max_rows=16384):
    """Feature-cache cold/warm ingest A/B for a GAME TRAINING dataset
    (ROADMAP item 4): round-trip ``data`` (capped at ``max_rows`` rows,
    recorded) through avro part files, then read them back cold
    (decode + cache build) and warm (mmap replay), asserting column-level
    wire parity between the two reads. Runs inside the config's obs
    session using DELTAS (no resets), so the fit telemetry that follows
    stays intact."""
    import shutil
    import tempfile

    import numpy as np

    from photon_tpu import obs
    from photon_tpu.cache import resolve_reader
    from photon_tpu.data.index_map import DefaultIndexMap, feature_key
    from photon_tpu.game.data import slice_game_data
    from photon_tpu.io.avro import write_avro_file
    from photon_tpu.io.data_reader import FeatureShardConfig
    from photon_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_tpu.obs import phase_summary

    n_ab = int(min(data.num_samples, max_rows))
    sub = slice_game_data(data, 0, n_ab)
    shard_names = sorted(sub.feature_shards)
    tags = sorted(sub.id_tags)
    d = tempfile.mkdtemp(prefix="bench-cache-ab-")
    saved_env = _pin_cache_env()
    try:
        # one avro bag holds every shard's features, namespaced by shard;
        # each shard's index map then selects exactly its own columns
        # back out (keys absent from a shard's map are dropped on read)
        def records(lo, hi):
            for i in range(lo, hi):
                feats = []
                for s in shard_names:
                    cols_i, vals_i = sub.feature_shards[s].row(i)
                    feats.extend(
                        {
                            "name": f"{s}:{int(c)}",
                            "term": "",
                            "value": float(v),
                        }
                        for c, v in zip(cols_i, vals_i)
                    )
                yield {
                    "uid": f"r{i}",
                    "label": float(sub.labels[i]),
                    "features": feats,
                    "metadataMap": {
                        t: str(sub.id_tags[t][i]) for t in tags
                    },
                    "weight": float(sub.weights[i]),
                    "offset": float(sub.offsets[i]),
                }

        t0 = time.perf_counter()
        parts = 4
        per = (n_ab + parts - 1) // parts
        for p in range(parts):
            write_avro_file(
                os.path.join(d, f"part-{p:05d}.avro"),
                TRAINING_EXAMPLE_AVRO,
                records(p * per, min((p + 1) * per, n_ab)),
            )
        gen_s = time.perf_counter() - t0
        shard_configs = {
            s: FeatureShardConfig(
                feature_bags=("features",), has_intercept=False
            )
            for s in shard_names
        }
        index_maps = {
            s: DefaultIndexMap(
                {
                    feature_key(f"{s}:{j}"): j
                    for j in range(sub.feature_shards[s].num_cols)
                }
            )
            for s in shard_names
        }

        def decode_count():
            return int(phase_summary().get("io.decode", {}).get("count", 0))

        def counters():
            return obs.get_registry().snapshot()["counters"]

        d0, c0 = decode_count(), counters()
        t1 = time.perf_counter()
        data_cold = resolve_reader(
            d, shard_configs, index_maps=index_maps, id_tags=tuple(tags),
            mode="rebuild",
        ).read()
        cold_s = time.perf_counter() - t1
        d1 = decode_count()
        t2 = time.perf_counter()
        data_warm = resolve_reader(
            d, shard_configs, index_maps=index_maps, id_tags=tuple(tags),
            mode="require",
        ).read()
        warm_s = time.perf_counter() - t2
        d2, c2 = decode_count(), counters()

        parity = 0.0
        for a, b in (
            (data_cold.labels, data_warm.labels),
            (data_cold.offsets, data_warm.offsets),
            (data_cold.weights, data_warm.weights),
        ):
            if n_ab:
                parity = max(parity, float(np.max(np.abs(a - b))))
        for s in shard_names:
            ma, mb = data_cold.feature_shards[s], data_warm.feature_shards[s]
            if not (
                np.array_equal(ma.indptr, mb.indptr)
                and np.array_equal(ma.indices, mb.indices)
            ):
                parity = float("inf")
            elif len(ma.values):
                parity = max(
                    parity, float(np.max(np.abs(ma.values - mb.values)))
                )
        for t in tags:
            if list(data_cold.id_tags[t]) != list(data_warm.id_tags[t]):
                parity = float("inf")
        return {
            "rows": n_ab,
            "avro_gen_s": round(gen_s, 3),
            "cold_ingest_s": round(cold_s, 4),  # decode + cache build
            "warm_ingest_s": round(warm_s, 4),  # mmap replay
            "ingest_speedup": round(cold_s / warm_s, 3) if warm_s else None,
            "parity_max_abs": parity,
            "warm_hit": int(
                c2.get("cache.hit", 0) - c0.get("cache.hit", 0)
            ),
            "warm_bytes": int(
                c2.get("cache.bytes", 0) - c0.get("cache.bytes", 0)
            ),
            "cold_decode_spans": d1 - d0,
            "warm_decode_spans": d2 - d1,
        }
    finally:
        os.environ.update(saved_env)
        shutil.rmtree(d, ignore_errors=True)


def _mesh_fleet_leg(worker, tmpdir, n, users):
    """The 2-process Gloo fleet leg of the mesh A/B (ISSUE 14): the SAME
    deterministic fit spans a 2-process × 2-virtual-device global mesh
    under ``jax.distributed`` with the fleet telemetry plane armed —
    per-process ``obs/p<k>`` artifacts, heartbeat snapshots, the
    per-sweep barrier-arrival log. The returned detail carries the
    per-sweep skew series (max skew ratio is band-gated: a healthy run
    flags ZERO stragglers) and the device-time
    compute / collectives / barrier breakdown the fit published from
    its own executables' comm census + cost-model flops."""
    import socket

    def _port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    coord_port = _port()
    out_root = os.path.join(tmpdir, "fleet_run")
    procs = []
    log_paths = []
    #: ambient fleet/obs knobs must not reach the workers (the repo's
    #: pin-ambient-env-out discipline): an exported PHOTON_OBS_PROCESS
    #: would make BOTH workers claim the same identity (flapping
    #: heartbeats, a one-process skew join that vacuously passes the
    #: band), an exported HTTP port would double-bind, and threshold
    #: exports would silently change what the band measures
    _FLEET_PINNED = (
        "PHOTON_FAULTS", "PHOTON_OBS_PROCESS", "PHOTON_OBS_FLEET",
        "PHOTON_OBS_HTTP_PORT", "PHOTON_FLEET_STRAGGLER_X",
        "PHOTON_FLEET_STALE_X",
    )
    for pid in range(2):
        env = {
            k: v
            for k, v in os.environ.items()
            if k != "XLA_FLAGS" and k not in _FLEET_PINNED
        }
        env["PHOTON_SANITIZE"] = "transfers"
        env["PHOTON_OBS_HEARTBEAT_S"] = "0.5"
        # worker output goes to FILES, never pipes: the two workers are
        # collectively coupled, and a chatty peer blocked on a full
        # 64 KiB pipe buffer stops entering collectives and deadlocks
        # the whole leg (the exact lesson scripts/live_probe.py records)
        log_path = os.path.join(tmpdir, f"fleet_p{pid}.log")
        log_paths.append(log_path)
        with open(log_path, "w") as log_f:
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, worker,
                        "--devices", "2",
                        "--num-processes", "2",
                        "--process-id", str(pid),
                        "--coordinator-port", str(coord_port),
                        "--out", os.path.join(tmpdir, f"fleet_p{pid}.json"),
                        "--out-root", out_root,
                        "--n", str(n),
                        "--users", str(users),
                    ],
                    stdout=log_f, stderr=subprocess.STDOUT, env=env,
                )
            )

    def _tail(pid):
        try:
            with open(log_paths[pid]) as f:
                return f.read()[-1200:]
        except OSError:
            return "(no log)"

    try:
        deadline = time.monotonic() + 900
        for p in procs:
            p.wait(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        return {"error": "fleet leg timed out after 900s"}
    for pid, p in enumerate(procs):
        if p.returncode != 0:
            return {
                "error": (
                    f"fleet worker {pid} failed rc={p.returncode}:\n"
                    f"{_tail(pid)}"
                )
            }
    with open(os.path.join(tmpdir, "fleet_p0.json")) as f:
        p0 = json.load(f)
    skew = p0.get("sweep_skew") or []
    bd = p0.get("device_breakdown") or {}
    return {
        "processes": 2,
        "devices_per_process": 2,
        "mesh_shape": p0.get("mesh_shape"),
        "sweeps_joined": len(skew),
        "max_skew_ratio": p0.get("max_skew_ratio"),
        "stragglers": p0.get("stragglers") or [],
        "steady_compiles": p0.get("steady_compiles"),
        "audit_findings": p0.get("audit_findings"),
        # the comm-vs-compute economics of the meshed sweep (the
        # scaling-limit metric, PAPERS.md): measured barrier fraction +
        # cost-model compute/collective split from the fit's own census
        "device_barrier_frac": bd.get("barrier_frac"),
        "device_compute_frac": bd.get("compute_frac"),
        "device_comm_frac": bd.get("comm_frac"),
        "sanitize": "transfers",
    }


def _mesh_scaling_ab(scale):
    """Meshed 1-vs-8 virtual-device GAME fit A/B (ROADMAP 1): two
    ``scripts/mesh_fit_worker.py`` subprocesses run the SAME deterministic
    FE + per-user-RE ``GameEstimator.fit(mesh=...)`` end-to-end — device
    count is fixed at process start, so a same-machine device-count A/B
    is necessarily two processes. Each leg runs under
    ``PHOTON_SANITIZE=transfers`` with every-sweep checkpoints (the
    meshed save path) and audits its OWN executables with the SPMD
    communication census; the row records mesh devices, priced
    comm bytes/sweep, per-device entity-table bytes (the ≈1/devices
    capacity claim, measured from live shards), f64 coefficient parity
    across device counts, and steady-state compile counts. On a 2-core
    builder 8 virtual devices TIME-SLICE the cores, so the wall-clock
    ratio is an honest same-machine number, not a scaling victory lap —
    the gated claims are parity, zero retraces, a clean audit and the
    table-shard ratio."""
    import shutil
    import tempfile

    import numpy as np

    n = {"smoke": 2048, "cpu": 4096, "tpu": 4096}[scale]
    users = {"smoke": 256, "cpu": 1024, "tpu": 1024}[scale]
    worker = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "mesh_fit_worker.py",
    )
    d = tempfile.mkdtemp(prefix="bench-mesh-ab-")
    legs: dict = {}
    npz: dict = {}
    try:
        for devs in (1, 8):
            out = os.path.join(d, f"leg{devs}.json")
            env = {
                k: v for k, v in os.environ.items() if k != "XLA_FLAGS"
            }
            env["PHOTON_SANITIZE"] = "transfers"
            try:
                res = subprocess.run(
                    [
                        sys.executable, worker,
                        "--devices", str(devs),
                        "--out", out,
                        "--n", str(n),
                        "--users", str(users),
                        "--checkpoint-dir", os.path.join(d, f"ckpt{devs}"),
                    ],
                    capture_output=True, text=True, timeout=900, env=env,
                )
            except subprocess.TimeoutExpired:
                # a wedged worker is a mesh-leg failure row (band-gated),
                # never an exception that aborts the whole config and
                # discards its fit/cache/obs results
                return {
                    "error": (
                        f"mesh worker devices={devs} timed out after 900s"
                    )
                }
            if res.returncode != 0:
                return {
                    "error": (
                        f"mesh worker devices={devs} failed:\n"
                        f"{res.stdout[-1200:]}\n{res.stderr[-1200:]}"
                    )
                }
            with open(out) as f:
                legs[devs] = json.load(f)
            npz[devs] = np.load(out + ".npz", allow_pickle=True)
        a, b = npz[1], npz[8]
        parity = float(np.max(np.abs(a["fe"] - b["fe"])))
        if list(a["re_keys"]) != list(b["re_keys"]):
            parity = float("inf")  # different entity sets: garbage
        else:
            parity = max(
                parity, float(np.max(np.abs(a["re_coefs"] - b["re_coefs"])))
            )
        s1 = legs[1]["steady_sweep_s"]
        s8 = legs[8]["steady_sweep_s"]
        b1 = legs[1]["entity_table_bytes_per_device"]
        b8 = legs[8]["entity_table_bytes_per_device"]
        fleet = _mesh_fleet_leg(worker, d, n, users)
        return {
            "fleet": fleet,
            "rows": n,
            "users": users,
            "devices": [1, 8],
            "mesh_shape": legs[8]["mesh_shape"],
            "steady_sweep_s_1dev": s1,
            "steady_sweep_s_8dev": s8,
            # same-machine ratio: virtual devices share the host cores,
            # so < 1 here is expected off real hardware — recorded, not
            # gated; efficiency = ratio / devices for the trend series
            "scaling_speedup": round(s1 / s8, 4) if s8 else None,
            "scaling_efficiency": round(s1 / s8 / 8, 4) if s8 else None,
            "comm_bytes_per_sweep": legs[8]["comm_bytes_per_sweep"],
            "entity_table_bytes_per_device": {"1": b1, "8": b8},
            "table_shard_ratio": round(b1 / b8, 3) if b8 else None,
            "steady_compiles": (
                legs[1]["steady_compiles"] + legs[8]["steady_compiles"]
            ),
            "audit_findings": (
                legs[1]["audit_findings"] + legs[8]["audit_findings"]
            ),
            "parity_max_abs": parity,
            "checkpointed": legs[8]["checkpointed"],
            "sanitize": "transfers",
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _run_game_config(
    *,
    n,
    fe_dim,
    fe_nnz,
    coords_spec,
    descent_iterations,
    fe_max_iter,
    re_max_iter,
    seed=0,
    config_name="game",
    cache_ingest_ab=False,
    mesh_scaling_ab=False,
):
    """Build skewed GAME data and run GameEstimator.fit; returns detail dict.

    ``coords_spec``: list of (name, num_entities, d_re, upper_bound).
    The FE shard is sparse when fe_nnz < fe_dim (AUTO picks the layout).

    Telemetry: the run executes with the obs spine enabled and exports a
    per-config run profile (Chrome trace + metrics + JSONL manifest)
    under ``$PHOTON_OBS_DIR`` (default ``bench_obs/``); the returned row
    carries the artifact paths and the per-phase wall split as ``obs``.
    """
    import numpy as np

    from photon_tpu import obs

    # one artifact set per config run: clean slate, then enable
    obs.reset()
    obs.enable()
    series_flusher = _start_series_flusher(config_name)

    from photon_tpu.game.config import (
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.game.data import (
        CSRMatrix,
        GameData,
        build_random_effect_dataset,
    )
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import (
        GLMProblemConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    # STRUCTURE (entity ids, sparse column patterns) comes from the fixed
    # seed so bucket/window shapes are stable and the persistent compile
    # cache hits across sessions; VALUES (features, labels) fold in
    # wall-clock entropy so that no replay of identical (executable,
    # inputs) executions can return a previous round's fit as a ~0 s wall.
    value_entropy = time.time_ns() & 0xFFFFFFFF
    vrng = np.random.default_rng(
        np.random.SeedSequence([seed + 1, value_entropy])
    )
    t0 = time.perf_counter()

    # --- fixed-effect shard (sparse CSR when fe_nnz < fe_dim) ----------
    if fe_nnz >= fe_dim:
        x = vrng.normal(size=(n, fe_dim)).astype(np.float32)
        fe_shard = CSRMatrix.from_dense(x)
        margin = x @ (0.1 * vrng.normal(size=fe_dim))
    else:
        indptr = np.arange(n + 1, dtype=np.int64) * fe_nnz
        cols = rng.integers(1, fe_dim, size=n * fe_nnz).astype(np.int32)
        cols[::fe_nnz] = 0  # intercept slot each row
        vals = (vrng.normal(size=n * fe_nnz) / np.sqrt(fe_nnz)).astype(
            np.float64
        )
        vals[::fe_nnz] = 1.0
        fe_shard = CSRMatrix(
            indptr=indptr, indices=cols, values=vals, num_cols=fe_dim
        )
        w_true = vrng.normal(size=fe_dim) * 0.3
        margin = np.zeros(n)
        np.add.at(
            margin, np.repeat(np.arange(n), fe_nnz), vals * w_true[cols]
        )

    labels = (vrng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        np.float64
    )

    shards = {"global": fe_shard}
    id_tags = {}
    coord_configs: dict = {}
    for name, num_entities, d_re, ub in coords_spec:
        ids = _zipf_ids(rng, n, num_entities)  # structure: seed-stable
        id_tags[name] = [f"{name[:1]}{i}" for i in ids]
        x_re = vrng.normal(size=(n, d_re)).astype(np.float32)
        shards[f"per_{name}"] = CSRMatrix.from_dense(x_re)
        coord_configs[name] = RandomEffectCoordinateConfig(
            random_effect_type=name,
            feature_shard=f"per_{name}",
            optimization=GLMProblemConfig(
                task=TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(
                    max_iterations=re_max_iter, ls_max_iterations=8
                ),
                regularization=RegularizationContext(RegularizationType.L2),
            ),
            regularization_weights=(1.0,),
            active_data_upper_bound=ub,
        )

    coord_configs["fixed"] = FixedEffectCoordinateConfig(
        feature_shard="global",
        optimization=GLMProblemConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer_config=OptimizerConfig(
                max_iterations=fe_max_iter, ls_max_iterations=10
            ),
            regularization=RegularizationContext(RegularizationType.L2),
        ),
        regularization_weights=(1.0,),
    )

    data = GameData.build(
        labels=labels, feature_shards=shards, id_tags=id_tags
    )
    data_build_s = time.perf_counter() - t0
    _log(f"[bench] game data build {data_build_s:.1f}s (n={n})")

    cache_detail = None
    if cache_ingest_ab:
        cache_detail = _cache_ingest_ab(data)
        _log(f"[bench] feature-cache ingest A/B: {cache_detail}")

    mesh_detail = None
    if mesh_scaling_ab:
        # subprocess legs (device count is fixed per process); runs
        # BEFORE the in-process fit so a wedged worker can't inherit a
        # partially-profiled obs state
        mesh_detail = _mesh_scaling_ab(mesh_scaling_ab)
        _log(f"[bench] mesh 1-vs-8 scaling A/B: {mesh_detail}")

    update_seq = ["fixed"] + [name for name, *_ in coords_spec]
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=coord_configs,
        update_sequence=update_seq,
        descent_iterations=descent_iterations,
        seed=seed,
        # overlap the cold compiles on a thread pool instead of paying
        # them serially inside the first sweep (game/descent.py)
        precompile=True,
    )

    # Projected cold-cache compile bill BEFORE anything is enqueued
    # (VERDICT r5 next #5): the pooled shape profile prices the programs
    # a fit will trace, so a budget-eating cold bill is visible up front
    # instead of inside the worker timeout.
    from photon_tpu.game.data import (
        _optimal_row_levels,
        _split_shape_budget,
        profile_random_effect_shapes,
        re_shape_budget,
    )
    from photon_tpu.game.descent import project_compile_bill
    from photon_tpu.util import compile_watch

    shape_pool = est._build_shape_pool(data)
    unpriced_coords = []
    if shape_pool is not None:
        n_solve_shapes = shape_pool.stats()["distinct_shapes"]
    else:
        # pool off (budget-disabled A/B) or no profilable coordinate:
        # price the per-coordinate fallback DP from the same profile
        # pass, so the budget-off projection doesn't silently drop the
        # dominant solve-shape term and report a bill that fits the
        # worker budget when the real one doesn't
        solve_shapes = set()
        for cname, ccfg in coord_configs.items():
            if not isinstance(ccfg, RandomEffectCoordinateConfig):
                continue
            prof = profile_random_effect_shapes(data, ccfg)
            if prof is None:
                unpriced_coords.append(cname)
                continue
            d_pad, n_trn = prof
            d_groups = np.unique(d_pad)
            gb = _split_shape_budget(
                re_shape_budget(ccfg.shape_budget), len(d_groups)
            )
            for dv in d_groups:
                levels = _optimal_row_levels(
                    n_trn[d_pad == dv], shape_budget=gb
                )
                solve_shapes |= {(int(lv), int(dv)) for lv in levels}
        n_solve_shapes = len(solve_shapes)
    projected_bill = project_compile_bill(
        2 * len(coord_configs),  # fused sweep + initial score each
        n_solve_shapes,
    )
    _log(f"[bench] projected cold-cache compile bill: {projected_bill}")
    if unpriced_coords:
        _log(
            "[bench] projection is a LOWER BOUND: coordinate(s) "
            f"{unpriced_coords} have unprofilable shards (solve shapes "
            "unpriced before build)"
        )

    t1 = time.perf_counter()
    with compile_watch.watch() as fit_compiles:
        # the pool priced above is injected so the fit neither re-profiles
        # nor can bucket differently from what the projection assumed
        result = est.fit(data, shape_pool=shape_pool)[0]
    fit_wall = time.perf_counter() - t1

    # Rebuild RE datasets (deterministic, same seed) for real-row accounting
    # and padding-waste reporting — WITH the same shape pool the fit's
    # builds used, so bucket partitions line up with the tracker infos.
    datasets = {
        name: build_random_effect_dataset(
            data, coord_configs[name], seed=seed, shape_pool=shape_pool
        )
        for name, *_ in coords_spec
    }
    waste = {}
    re_state = {}
    for name, ds in datasets.items():
        w = ds.padding_waste()
        waste[name] = {
            "buckets": [b["shape"] for b in w["buckets"]],
            "total_waste": round(w["total_waste"], 4),
        }
        coeffs = sum(
            b.features.shape[0] * b.features.shape[2] for b in ds.buckets
        )
        dev_bytes = sum(
            b.features.size * 4
            + 3 * b.labels.size * 4
            + b.labels.size * 4
            + b.score_feats.size * 4
            + 2 * b.score_pos.size * 4
            for b in ds.buckets
        )
        re_state[name] = {
            "num_entities": int(ds.num_entities),
            "re_coefficients": int(coeffs),
            "device_bucket_bytes": int(dev_bytes),
            "active_samples": int(ds.total_active_samples()),
        }

    # full-model scoring + device grouped evaluation (per-entity AUC over
    # every entity of the first RE coordinate — the MultiEvaluator lexsort/
    # segment kernels at bench scale)
    t0 = time.perf_counter()
    with obs.span("bench.score"):
        scores = np.asarray(result.model.score(data))
    score_wall = time.perf_counter() - t0
    from photon_tpu.evaluation import MultiEvaluator

    first_re = coords_spec[0][0]
    ev_fn = MultiEvaluator.auc(first_re)
    ev_ids = np.asarray(id_tags[first_re])
    # warm-up at full shape with perturbed scores, so that no cold
    # compile is billed as "evaluation wall"; the perturbation also keeps
    # warm≠timed inputs, so the timed call cannot be a replay
    _ = ev_fn(
        scores + 1e-6 * np.random.default_rng(1).normal(size=scores.shape),
        labels,
        ev_ids,
    )
    t0 = time.perf_counter()
    with obs.span("bench.grouped_eval"):
        grouped_auc = ev_fn(scores, labels, ev_ids)
    grouped_wall = time.perf_counter() - t0

    # steady-state sweep time: tracker iterations >= 1 (iteration 0 pays
    # compiles); falls back to all iterations when only one ran. Under the
    # default "sweep" tracker granularity the honest (barrier-closed)
    # walls live in the per-sweep rows; per-coordinate rows carry ENQUEUE
    # walls only (the sync-free steady state pays one read-back per sweep,
    # game/descent.py).
    it_rows = [r for r in result.tracker if "coordinate" in r]
    sweep_rows = [r for r in result.tracker if "sweep_seconds" in r]
    steady = [r for r in it_rows if r["iteration"] >= 1]
    measured = steady if steady else it_rows
    steady_sweeps = [r for r in sweep_rows if r["iteration"] >= 1]
    measured_sweep_rows = steady_sweeps if steady_sweeps else sweep_rows
    if measured_sweep_rows:
        measured_sweeps = len(measured_sweep_rows)
        steady_s = sum(r["sweep_seconds"] for r in measured_sweep_rows)
        sweep_barrier_s = sum(
            r.get("barrier_seconds", 0.0) for r in measured_sweep_rows
        )
        dispatches_per_sweep = sum(
            r["dispatches"] for r in measured_sweep_rows
        ) / measured_sweeps
        granularity = measured_sweep_rows[0].get("granularity")
    else:
        # defensive guard only: the current descent appends a per-sweep
        # row under BOTH granularities, so this is unreachable for any
        # tracker it produces (it would take a zero-iteration run or a
        # pre-r6 tracker format). Fall back to per-coordinate walls.
        measured_sweeps = len({r["iteration"] for r in measured})
        steady_s = sum(r["seconds"] for r in measured)
        sweep_barrier_s = None
        dispatches_per_sweep = None
        granularity = None
    steady_examples = _game_examples_from_tracker(measured, datasets, n)
    total_examples = sum(v["examples"] for v in steady_examples.values())
    total_touched = sum(
        v["examples_touched"] for v in steady_examples.values()
    )

    # compile split: warm = compile seconds that leaked into the measured
    # steady-state sweeps (must be ~0 — nonzero means retracing in the
    # hot loop), cold = everything else the fit paid (precompile pass +
    # first-sweep compiles + initial scoring)
    warm_compile_s = sum(
        r.get("compile_seconds", 0.0) for r in measured_sweep_rows
    )
    warm_compiles = sum(r.get("compiles", 0) for r in measured_sweep_rows)
    shape_sets = {name: ds.shape_stats() for name, ds in datasets.items()}
    compile_detail = {
        "n_programs_compiled": fit_compiles["backend_compiles"],
        "compile_wall_s": fit_compiles["backend_compile_s"],
        "compile_wall_s_cold": round(
            fit_compiles["backend_compile_s"] - warm_compile_s, 4
        ),
        "compile_wall_s_warm": round(warm_compile_s, 4),
        "n_programs_compiled_warm": warm_compiles,
        "cache_hits": fit_compiles["cache_hits"],
        "cache_misses": fit_compiles["cache_misses"],
        "projected": projected_bill,
        "precompile": (result.compile_stats or {}).get("precompile"),
        "solve_shapes": {
            **shape_sets,
            "distinct_global": len(
                {
                    tuple(s)
                    for st in shape_sets.values()
                    for s in st["shapes"]
                }
            ),
        },
    }

    # telemetry artifacts: one Chrome trace + metrics snapshot + JSONL
    # manifest + summary per config (open the trace at
    # https://ui.perfetto.dev), plus the per-phase wall split inline in
    # the row — same exporter the CLI drivers use
    from photon_tpu.obs import phase_summary, summary_table

    obs_dir = os.environ.get("PHOTON_OBS_DIR", "bench_obs")
    series_path = _stop_series_flusher(series_flusher)
    paths = obs.export_artifacts(
        obs_dir,
        prefix=f"{config_name}.",
        meta={"config": config_name, "n": n},
    )
    obs_detail = {
        "trace_path": paths["trace"],
        "metrics_path": paths["metrics"],
        "manifest_path": paths["manifest"],
        "memory_path": paths["memory"],
        "series_path": series_path,
        "phase_wall_s": {
            name: agg["total_s"] for name, agg in phase_summary().items()
        },
    }
    # device-memory ledger columns (metric_version 4): the live-census
    # high-watermark, XLA's per-executable scratch total, and the
    # transfer bill — read BEFORE obs.reset() drops the run state
    mem_report = obs.memory.get_ledger().report()
    mem_detail = {
        "peak_bytes": mem_report["peak_live_bytes"],
        "exec_temp_bytes": mem_report["executables_total"]["temp_bytes"],
        "exec_argument_bytes": mem_report["executables_total"][
            "argument_bytes"
        ],
        "n_executables_analyzed": mem_report["executables_total"][
            "n_analyzed"
        ],
        "h2d_bytes": mem_report["h2d_bytes"],
        "d2h_bytes": mem_report["d2h_bytes"],
    }
    _log("[bench] run profile:\n" + summary_table())
    _log(f"[bench] memory ledger: {mem_detail}")
    # artifact written — telemetry back off so non-GAME configs run (and
    # are timed) unprofiled, and spans don't accumulate across configs
    obs.disable()
    obs.reset()

    return {
        "n": n,
        "fe_dim": fe_dim,
        "fe_nnz": fe_nnz,
        "value_entropy": value_entropy,
        "obs": obs_detail,
        "mem": mem_detail,
        "cache": cache_detail,
        "mesh": mesh_detail,
        "fe_layout": "sparse_ell" if fe_nnz < fe_dim else "dense",
        "coordinates": {
            name: {"num_entities": ne, "d_re": dr, "active_upper_bound": ub}
            for name, ne, dr, ub in coords_spec
        },
        "descent_iterations": descent_iterations,
        "measured_sweeps": measured_sweeps,
        "data_build_s": round(data_build_s, 2),
        "fit_wall_s": round(fit_wall, 2),
        "full_score_s": round(score_wall, 3),
        "grouped_auc": {
            "per": first_re,
            "value": round(float(grouped_auc), 4),
            "wall_s": round(grouped_wall, 3),
        },
        "steady_sweep_s": round(steady_s, 4),
        # dispatch/sync profile of the measured window (fused sweep:
        # 1 program per coordinate per sweep + one read-back barrier)
        "dispatches_per_sweep": dispatches_per_sweep,
        "sweep_barrier_s": round(sweep_barrier_s, 4)
        if sweep_barrier_s is not None
        else None,
        "tracker_granularity": granularity,
        "examples_per_sec": round(total_examples / steady_s, 1)
        if steady_s > 0
        else None,
        # the r4-comparable series: padded block rows the solver touched
        # (METRIC_VERSION docstring) — touched/active shows the padding
        # amplification the shape budget trades against program count
        "examples_per_sec_touched": round(total_touched / steady_s, 1)
        if steady_s > 0
        else None,
        # measured (steady) window only — the same window
        # examples_per_sec and the Spark model cover. Under "sweep"
        # granularity the per-coordinate seconds are ENQUEUE walls
        # (relative split only); the honest wall is steady_sweep_s.
        "per_coordinate": {
            cid: {
                "seconds": round(v["seconds"], 4),
                "examples": v["examples"],
                "examples_touched": v["examples_touched"],
                "n_evals": v["evals"],
            }
            for cid, v in steady_examples.items()
        },
        "compile": compile_detail,
        "padding_waste": waste,
        "re_state": re_state,
    }


def config_glmix_estimator(peak_flops, scale):
    """BASELINE config 4: FE + per-user RE through GameEstimator.fit with
    Zipf-skewed users — the number includes bucketing, padding waste,
    scatter scoring, and CD control flow (VERDICT r2 weak #2)."""
    del peak_flops
    return _run_game_config(
        n=_pick(scale, 1 << 12, 1 << 15, 1 << 17),
        fe_dim=_pick(scale, 32, 128, 128),
        fe_nnz=1 << 30,  # dense
        coords_spec=_pick(
            scale,
            [("user", 128, 8, 64)],
            [("user", 2048, 16, 512)],
            [("user", 8192, 16, 1024)],
        ),
        descent_iterations=_pick(scale, 2, 3, 3),
        fe_max_iter=_pick(scale, 5, 20, 20),
        re_max_iter=_pick(scale, 3, 10, 10),
        config_name="glmix_game_estimator",
        # the feature-cache cold/warm ingest A/B rides the GLMix config:
        # training pays the same decode+assembly every run (ROADMAP 4)
        cache_ingest_ab=True,
        # the meshed 1-vs-8 virtual-device scaling A/B rides here too
        # (ROADMAP 1): parity, comm census, per-device table bytes and
        # zero-retrace are QUALITY_BANDS gates; wall ratio is recorded
        mesh_scaling_ab=scale,
    )


def config_game_ctr_scale(peak_flops, scale):
    """BASELINE config 5: sparse FE + per-user RE (2^20 users) + per-item RE
    (2^17 items) at CTR shape — the entity-axis scale demonstration
    (VERDICT r2 weak #4 / missing #2)."""
    del peak_flops
    return _run_game_config(
        n=_pick(scale, 1 << 13, 1 << 18, 1 << 21),
        fe_dim=_pick(scale, 1 << 10, 1 << 14, 1 << 17),
        fe_nnz=_pick(scale, 8, 24, 24),
        coords_spec=_pick(
            scale,
            [("user", 1 << 10, 8, 32), ("item", 1 << 8, 8, 128)],
            [("user", 1 << 16, 16, 128), ("item", 1 << 13, 16, 512)],
            [("user", 1 << 20, 16, 256), ("item", 1 << 17, 16, 1024)],
        ),
        descent_iterations=2,  # iteration 1 = steady state (post-compile)
        fe_max_iter=_pick(scale, 4, 8, 10),
        re_max_iter=_pick(scale, 3, 4, 5),
        config_name="game_ctr_scale",
    )


# ---------------------------------------------------------------------------
# Config 6 — streaming GAME inference throughput (scoring, not training):
# avro part files → chunked decode → ONE fused precompiled device program
# per batch → sharded avro score output, double-buffered (game/scoring.py),
# A/B'd on the same files against the monolithic materialize-everything
# path. Parity and zero-steady-state-retrace are QUALITY_BANDS gates.
# ---------------------------------------------------------------------------


def config_scoring_stream(peak_flops, scale):
    del peak_flops
    import shutil
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from photon_tpu import obs
    from photon_tpu.data.index_map import DefaultIndexMap
    from photon_tpu.game.model import (
        BucketCoefficients,
        FixedEffectModel,
        GameModel,
        MatrixFactorizationModel,
        RandomEffectModel,
    )
    from photon_tpu.game.scoring import GameScorer
    from photon_tpu.game.transformer import GameTransformer
    from photon_tpu.io.avro import write_avro_file
    from photon_tpu.io.data_reader import AvroDataReader, FeatureShardConfig
    from photon_tpu.io.model_io import (
        ShardedScoringWriter,
        save_scoring_results,
    )
    from photon_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.glm import model_for_task
    from photon_tpu.types import TaskType
    from photon_tpu.util import compile_watch

    # CTR-shape GAME model (what config-5 trains): FE + per-user RE +
    # per-item RE + user×item MF — the monolithic host path pays each
    # coordinate's score serially after the full read, while the fused
    # engine computes all four in one dispatch, overlapped with decode
    n, d, nnz, users, items, batch_rows, parts_in, parts_out = _pick(
        scale,
        (1 << 12, 16, 8, 64, 16, 512, 4, 2),
        (1 << 15, 32, 16, 2048, 256, 8192, 8, 4),
        (1 << 20, 64, 24, 1 << 16, 4096, 16384, 16, 8),
    )
    mf_k = 8
    seed = 6
    # STRUCTURE (entity ids, column patterns) from the fixed seed so batch
    # shapes are stable; VALUES (features, labels, model weights) fold in
    # wall-clock entropy (recorded as value_entropy) so that no replay
    # cache can answer from a previous round
    rng = np.random.default_rng(seed)
    value_entropy = time.time_ns() & 0xFFFFFFFF
    vrng = np.random.default_rng(
        np.random.SeedSequence([seed + 1, value_entropy])
    )
    ids = rng.integers(0, users, size=n)
    item_ids = rng.integers(0, items, size=n)
    cols = np.sort(np.argsort(rng.random((n, d)), axis=1)[:, :nnz], axis=1)
    vals = vrng.normal(size=(n, nnz)) / np.sqrt(nnz)
    w_fe = vrng.normal(size=d) * 0.5
    w_re = vrng.normal(size=(users, d)) * 0.5
    w_it = vrng.normal(size=(items, d)) * 0.5
    uf = vrng.normal(size=(users, mf_k)) * 0.3
    vf = vrng.normal(size=(items, mf_k)) * 0.3
    margin = (
        np.einsum("nk,nk->n", vals, w_fe[cols])
        + np.einsum("nk,nk->n", vals, w_re[ids[:, None], cols])
        + np.einsum("nk,nk->n", vals, w_it[item_ids[:, None], cols])
        + np.einsum("nk,nk->n", uf[ids], vf[item_ids])
    )
    labels = (vrng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        float
    )

    in_dir = tempfile.mkdtemp(prefix="bench-scoring-in-")
    out_root = tempfile.mkdtemp(prefix="bench-scoring-out-")
    try:
        t0 = time.perf_counter()
        per_part = (n + parts_in - 1) // parts_in
        for p in range(parts_in):
            lo, hi = p * per_part, min((p + 1) * per_part, n)
            write_avro_file(
                os.path.join(in_dir, f"part-{p:05d}.avro"),
                TRAINING_EXAMPLE_AVRO,
                (
                    {
                        "uid": f"s{i}",
                        "label": float(labels[i]),
                        "features": [
                            {
                                "name": f"f{int(c)}",
                                "term": "",
                                "value": float(v),
                            }
                            for c, v in zip(cols[i], vals[i])
                        ],
                        "metadataMap": {
                            "userId": f"u{int(ids[i])}",
                            "itemId": f"it{int(item_ids[i])}",
                        },
                        "weight": 1.0,
                        "offset": 0.0,
                    }
                    for i in range(lo, hi)
                ),
            )
        gen_s = time.perf_counter() - t0

        # model in the index map's feature order (from_keys sorts the
        # name⊕term keys the reader looks up)
        from photon_tpu.data.index_map import feature_key

        imap = DefaultIndexMap.from_keys(
            [feature_key(f"f{j}") for j in range(d)], add_intercept=False
        )
        perm = np.array([imap.get_index(feature_key(f"f{j}")) for j in range(d)])
        w_vec = np.zeros(d)
        w_vec[perm] = w_fe
        def random_effect(tag, prefix, id_width, coefs):
            e_n = len(coefs)
            aligned = np.zeros((e_n, d))
            aligned[:, perm] = coefs
            vocab = np.array(sorted(f"{prefix}{i}" for i in range(e_n)))
            return RandomEffectModel(
                random_effect_type=tag,
                feature_shard="global",
                task=task,
                vocab=vocab,
                buckets=(
                    BucketCoefficients(
                        entity_ids=np.arange(e_n, dtype=np.int64),
                        col_index=np.tile(
                            np.arange(d, dtype=np.int64), (e_n, 1)
                        ),
                        coefficients=aligned[
                            [int(k[id_width:]) for k in vocab]
                        ],
                    ),
                ),
                num_features=d,
            )

        task = TaskType.LOGISTIC_REGRESSION
        model = GameModel(
            coordinates={
                "fixed": FixedEffectModel(
                    model=model_for_task(
                        task, Coefficients(means=jnp.asarray(w_vec))
                    ),
                    feature_shard="global",
                ),
                "per-user": random_effect("userId", "u", 1, w_re),
                "per-item": random_effect("itemId", "it", 2, w_it),
                "mf": MatrixFactorizationModel(
                    row_entity_type="userId",
                    col_entity_type="itemId",
                    row_vocab=np.array([f"u{i}" for i in range(users)]),
                    col_vocab=np.array([f"it{i}" for i in range(items)]),
                    row_factors=uf,
                    col_factors=vf,
                ),
            },
            task=task,
        )
        shard_configs = {
            "global": FeatureShardConfig(
                feature_bags=("features",), has_intercept=False
            )
        }
        transformer = GameTransformer(model=model, task=task)
        scorer = GameScorer(model, batch_rows=batch_rows)
        aot = scorer.precompile(ell_widths={"global": nnz})

        counter = {"s": 0, "m": 0}

        def run_stream(chunk_source=None):
            if chunk_source is None:
                reader = AvroDataReader(index_maps={"global": imap})
                chunks = reader.iter_chunks(
                    in_dir, shard_configs, id_tags=("userId", "itemId"),
                    chunk_rows=batch_rows,
                )
            else:
                chunks = chunk_source()
            sdir = os.path.join(out_root, f"stream-{counter['s']}")
            counter["s"] += 1
            writer = ShardedScoringWriter(
                sdir, num_partitions=parts_out, model_id="bench"
            )
            t0 = time.perf_counter()
            res = scorer.stream(
                chunks,
                on_batch=lambda c, s: writer.write_chunk(
                    s, labels=c.labels, weights=c.weights, uids=c.uids
                ),
            )
            writer.close()
            return res, time.perf_counter() - t0

        def run_mono():
            reader = AvroDataReader(index_maps={"global": imap})
            mdir = os.path.join(out_root, f"mono-{counter['m']}")
            counter["m"] += 1
            t0 = time.perf_counter()
            data = reader.read(in_dir, shard_configs, id_tags=("userId", "itemId"))
            scores = np.asarray(transformer.score(data))
            save_scoring_results(
                os.path.join(mdir, "part-00000.avro"),
                scores,
                model_id="bench",
                labels=data.labels,
                weights=data.weights,
                uids=data.uids,
            )
            return scores, time.perf_counter() - t0

        # Warmup pass for BOTH sides (cold stats recorded from the stream
        # side), then ABBA measured runs — mono, stream, stream, mono —
        # so neither side systematically runs on a warmer page cache and
        # both medians come from warm-state runs (the small-delta
        # methodology from PERF.md r7: same-state A/B, medians, and the
        # paired walls recorded so a reader can judge the noise floor).
        s1, s1_wall = run_stream()
        _, m0_wall = run_mono()  # mono warmup (discarded from the median)
        _, m1_wall = run_mono()
        obs.reset()
        obs.enable()
        series_flusher = _start_series_flusher("game_scoring_stream")
        cw_before = compile_watch.snapshot()
        s2, s2_wall = run_stream()
        steady_compiles = compile_watch.delta(cw_before)["backend_compiles"]
        from photon_tpu.obs import phase_summary, summary_table

        obs_dir = os.environ.get("PHOTON_OBS_DIR", "bench_obs")
        series_path = _stop_series_flusher(series_flusher)
        paths = obs.export_artifacts(
            obs_dir,
            prefix="game_scoring_stream.",
            meta={"config": "game_scoring_stream", "n": n},
        )
        obs_detail = {
            "trace_path": paths["trace"],
            "metrics_path": paths["metrics"],
            "manifest_path": paths["manifest"],
            "memory_path": paths["memory"],
            "series_path": series_path,
            "phase_wall_s": {
                name: agg["total_s"]
                for name, agg in phase_summary().items()
            },
        }
        # memory ledger columns for the measured warm stream (the AOT
        # score executable's static footprint rides along from the
        # precompile above — it survives obs.reset by design)
        mem_report = obs.memory.get_ledger().report()
        mem_detail = {
            "peak_bytes": mem_report["peak_live_bytes"],
            "exec_temp_bytes": mem_report["executables_total"][
                "temp_bytes"
            ],
            "n_executables_analyzed": mem_report["executables_total"][
                "n_analyzed"
            ],
            "h2d_bytes": mem_report["h2d_bytes"],
            "d2h_bytes": mem_report["d2h_bytes"],
        }
        _log("[bench] scoring run profile:\n" + summary_table())
        _log(f"[bench] memory ledger: {mem_detail}")
        obs.disable()
        obs.reset()
        m2_scores, m2_wall = run_mono()

        # --- feature-cache cold/warm ingest A/B (ROADMAP item 4) -------
        # cold: decode avro once while BUILDING the columnar cache
        # through the same stream; warm: replay the mmap cache (the
        # producer becomes mmap slice + H2D copy). Same fused engine on
        # both sides, so wire-parity is exact-float and the speedup is
        # pure ingest. io.decode span counts are recorded per side — the
        # warm side must show ZERO (quality-band gated).
        from photon_tpu.cache import resolve_reader
        from photon_tpu.obs import phase_summary as _cache_phases

        def run_cache_stream(mode):
            # the wall INCLUDES resolve_reader — open, column size
            # checks, and the source-file sha256 re-hash are what a real
            # warm driver run pays before its first chunk, so excluding
            # them would overstate the warm win (the glmix ingest A/B
            # times the same way)
            t0 = time.perf_counter()
            resolved = resolve_reader(
                in_dir,
                shard_configs,
                index_maps={"global": imap},
                id_tags=("userId", "itemId"),
                mode=mode,
            )
            res, _ = run_stream(
                chunk_source=lambda: resolved.iter_chunks(
                    chunk_rows=batch_rows
                )
            )
            return res, time.perf_counter() - t0

        saved_cache_env = _pin_cache_env()
        obs.reset()
        obs.enable()
        try:
            s_cold, cache_cold_wall = run_cache_stream("rebuild")
            cold_decode_spans = int(
                _cache_phases().get("io.decode", {}).get("count", 0)
            )
            obs.reset()
            s_warm, cache_warm_wall = run_cache_stream("require")
            warm_decode_spans = int(
                _cache_phases().get("io.decode", {}).get("count", 0)
            )
            cache_counters = obs.get_registry().snapshot()["counters"]
        finally:
            os.environ.update(saved_cache_env)
            obs.disable()
            obs.reset()
        cache_warm_sps = n / cache_warm_wall

        denom = 1.0 + np.abs(m2_scores)
        max_abs = float(np.max(np.abs(s2.scores - m2_scores)))
        max_rel = float(np.max(np.abs(s2.scores - m2_scores) / denom))
        mono_wall = float(np.median([m1_wall, m2_wall]))
        stream_sps = n / s2_wall
        mono_sps = n / mono_wall
        return {
            "n": n,
            "d": d,
            "nnz_per_row": nnz,
            "num_users": users,
            "num_items": items,
            "mf_factors": mf_k,
            "batch_rows": batch_rows,
            "input_parts": parts_in,
            "output_partitions": parts_out,
            "value_entropy": value_entropy,
            "input_gen_s": round(gen_s, 2),
            "aot_precompile": {
                k: aot[k]
                for k in (
                    "wall_s", "backend_compile_s", "cache_hits",
                    "cache_misses",
                )
            },
            "cold": {
                "wall_s": round(s1_wall, 4),
                "first_batch_s": round(s1.stats.batch_walls_s[0], 4),
                "compiles": s1.stats.compiles["backend_compiles"],
                "compile_s": s1.stats.compiles["backend_compile_s"],
            },
            "warm": {
                "wall_s": round(s2_wall, 4),
                "batch_latency_s": s2.stats.latency_percentiles(),
                "samples_per_sec": round(stream_sps, 1),
            },
            "steady_compiles": int(steady_compiles),
            "max_staged_chunks": s2.stats.max_staged_chunks,
            "monolithic": {
                "walls_s": [round(m1_wall, 4), round(m2_wall, 4)],
                "samples_per_sec": round(mono_sps, 1),
            },
            "parity": {
                "max_abs_diff": max_abs,
                "max_rel_diff": max_rel,
            },
            "speedup_vs_monolithic": round(stream_sps / mono_sps, 3),
            "examples_per_sec": round(stream_sps, 1),
            "cache": {
                "cold_wall_s": round(cache_cold_wall, 4),
                "warm_wall_s": round(cache_warm_wall, 4),
                "warm_samples_per_sec": round(cache_warm_sps, 1),
                "warm_speedup_vs_avro_stream": round(
                    cache_warm_sps / stream_sps, 3
                ),
                "parity_max_abs": float(
                    np.max(np.abs(s_warm.scores - s_cold.scores))
                ),
                "warm_hit": int(cache_counters.get("cache.hit", 0)),
                "warm_bytes": int(cache_counters.get("cache.bytes", 0)),
                "cold_decode_spans": cold_decode_spans,
                "warm_decode_spans": warm_decode_spans,
            },
            "obs": obs_detail,
            "mem": mem_detail,
        }
    finally:
        shutil.rmtree(in_dir, ignore_errors=True)
        shutil.rmtree(out_root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Config 7 — tail latency under Poisson load (ROADMAP 2 / ISSUE 15):
# the open-loop load harness (scripts/load_harness.py) drives the
# streaming scorer with seeded exponential inter-arrivals — arrivals
# decoupled from completions, each request's latency clock starting at
# its SCHEDULED arrival (queueing counts; no coordinated omission) —
# and reports the sustained-QPS vs tail-latency curve. The armed SLO
# gates the run (QUALITY_BANDS: p99 wall band + the gate verdict).
# ---------------------------------------------------------------------------


def config_scoring_tail(peak_flops, scale):
    del peak_flops
    from photon_tpu import obs

    scripts_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"
    )
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    import load_harness

    num_requests, batch_rows, users, d, nnz = _pick(
        scale,
        (16, 256, 64, 16, 8),
        (48, 2048, 512, 32, 16),
        (64, 8192, 4096, 64, 24),
    )
    # the spec is deliberately loose at bench scale: it gates "the tail
    # did not detonate under sustained sub-capacity load" (a stall, a
    # retrace, a backed-up queue), not a hero number — the harness CLI
    # is where tight-budget experiments run
    spec = "p99<=5s@60s"
    obs_dir = os.environ.get("PHOTON_OBS_DIR", "bench_obs")
    series_flusher = _start_series_flusher("game_scoring_tail")
    try:
        doc = load_harness.run_load(
            "auto",
            num_requests=num_requests,
            batch_rows=batch_rows,
            spec=spec,
            seed=15,
            out_dir=obs_dir,
            prefix="game_scoring_tail.",
            workload_kwargs={"users": users, "d": d, "nnz": nnz},
        )
    finally:
        series_path = _stop_series_flusher(series_flusher)
        obs.reset()
    paths = doc["artifacts"]
    sustained = doc["legs"][0]
    top = doc["legs"][-1]

    # trace-overhead A/B (ISSUE 19): the identical paced leg twice over a
    # fresh workload — causal trace plane disarmed, then armed at
    # sample_n=1 so EVERY request records its full event chain (worst-case
    # record volume, no sampling relief). Banded as a fraction of the
    # disarmed p99 (trace_overhead_p99_frac_max) — the claim under gate is
    # "arming tracing does not detonate the tail", measured on the same
    # Poisson schedule both sides.
    from photon_tpu.obs import causal as obs_causal

    ab_requests = min(num_requests, 24)
    ab_qps = 0.5 * doc["capacity_qps"]
    scorer_ab, chunks_ab = load_harness.build_workload(
        num_requests=ab_requests,
        batch_rows=batch_rows,
        d=d,
        nnz=nnz,
        users=users,
        seed=16,
    )
    # pin the env so PHOTON_TRACE=1 in the caller's shell cannot re-arm
    # the "off" leg through the scorer's ensure_from_env() hook
    saved_trace_env = os.environ.pop("PHOTON_TRACE", None)
    try:
        obs_causal.clear()
        leg_off = load_harness.run_leg(
            scorer_ab, chunks_ab, qps=ab_qps, seed=16
        )
        obs_causal.install(sample_n=1)
        leg_on = load_harness.run_leg(
            scorer_ab, chunks_ab, qps=ab_qps, seed=16
        )
    finally:
        obs_causal.clear()
        obs.reset()
        if saved_trace_env is not None:
            os.environ["PHOTON_TRACE"] = saved_trace_env
    p99_off = leg_off["latency_s"].get("p99")
    p99_on = leg_on["latency_s"].get("p99")
    trace_delta_frac = (
        round((p99_on - p99_off) / p99_off, 4)
        if p99_on is not None and p99_off
        else None
    )
    return {
        "n": num_requests * batch_rows,
        "batch_rows": batch_rows,
        "num_requests": num_requests,
        "spec": doc["spec"],
        "capacity_qps": doc["capacity_qps"],
        "points": doc["legs"],
        # the banded headline: the SUSTAINED (0.5× capacity) leg's tail
        "tail": {
            "offered_qps": sustained["offered_qps"],
            "p50_s": sustained["latency_s"].get("p50"),
            "p99_s": sustained["latency_s"].get("p99"),
            "p99_9_s": sustained["latency_s"].get("p99.9"),
            "violations": sustained["violations"],
            "violations_by_stage": sustained["violations_by_stage"],
            "gate_ok": sustained["gate_ok"],
            "slo_violations": sustained["slo_violations"],
        },
        "examples_per_sec": top["samples_per_sec"],
        "trace_overhead": {
            "requests": ab_requests,
            "offered_qps": round(ab_qps, 3),
            "sample_n": 1,
            "p99_off_s": p99_off,
            "p99_on_s": p99_on,
            "p99_delta_frac": trace_delta_frac,
        },
        "obs": {
            "slo_report_path": paths.get("slo"),
            "metrics_path": paths.get("metrics"),
            "series_path": series_path,
        },
    }


# ---------------------------------------------------------------------------
# Config: serving hot swap under load (ISSUE 16). Sustained paced traffic
# through the always-on engine; one zero-downtime model hot swap lands
# mid-run. Records the swap wall, how many requests were in flight at the
# flip, shed/failed counts, and post-swap bit parity vs a cold scorer on
# the new model. QUALITY_BANDS: zero failed requests, parity <= 1e-6.
# ---------------------------------------------------------------------------


def config_game_serving_swap(peak_flops, scale):
    del peak_flops
    import numpy as np

    from photon_tpu import obs
    from photon_tpu.game.data import slice_game_data
    from photon_tpu.serve.admission import AdmissionQueue
    from photon_tpu.serve.engine import ServingEngine
    from photon_tpu.serve.registry import ModelRegistry, model_fingerprint

    scripts_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"
    )
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    import load_harness

    num_requests, batch_rows, users, d, nnz = _pick(
        scale,
        (24, 128, 64, 16, 8),
        (96, 1024, 512, 32, 16),
        (128, 4096, 4096, 64, 24),
    )
    rows_per_req = max(8, batch_rows // 4)
    qps = _pick(scale, 40.0, 24.0, 24.0)

    obs.enable()
    try:
        scorer_a, chunks = load_harness.build_workload(
            num_requests=num_requests,
            batch_rows=batch_rows,
            d=d,
            nnz=nnz,
            users=users,
            seed=16,
        )
        scorer_b, _ = load_harness.build_workload(
            num_requests=num_requests,
            batch_rows=batch_rows,
            d=d,
            nnz=nnz,
            users=users,
            seed=17,
        )
        requests = [slice_game_data(c, 0, rows_per_req) for c in chunks]
        # cold oracles BEFORE the traffic window: their compiles must not
        # pollute the engine's zero-traffic-compile accounting
        exp_a = [scorer_a.score_data(r) for r in requests]
        exp_b = [scorer_b.score_data(r) for r in requests]
        fp_b = model_fingerprint(scorer_b.model)

        reg = ModelRegistry()
        reg.register(
            "default",
            scorer_a.model,
            batch_rows=batch_rows,
            ell_widths={"global": nnz},
        )
        queue = AdmissionQueue(
            cap=max(64, num_requests), default_deadline_s=120.0,
            max_rows=batch_rows,
        )
        engine = ServingEngine(
            reg, queue, batch_rows=batch_rows, poll_s=0.005
        )
        engine.start()

        flip_at = num_requests // 2
        interval = 1.0 / qps
        futures, post_flip, swap = [], [], None
        t_run0 = time.perf_counter()
        for i, req in enumerate(requests):
            if i == flip_at:
                t_sw0 = time.perf_counter()
                staged = reg.begin_swap(
                    "default", scorer_b.model, expect_fingerprint=fp_b
                )
                while reg.has_pending_swap("default"):
                    if time.perf_counter() - t_sw0 > 60:
                        raise RuntimeError("engine never applied the flip")
                    time.sleep(0.0005)
                in_flight_at_flip = sum(
                    1 for f in futures if not f.done()
                ) + reg.in_flight("default")
                swap = {
                    "swap_wall_s": round(time.perf_counter() - t_sw0, 6),
                    "build_wall_s": staged["build_wall_s"],
                    "in_flight_at_flip": in_flight_at_flip,
                    "table_bytes": staged["table_bytes"],
                }
            fut = queue.submit(req, arrival_t=time.perf_counter())
            futures.append(fut)
            if swap is not None and i >= flip_at:
                post_flip.append((i, fut))
            target = t_run0 + (i + 1) * interval
            lag = target - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
        stats = engine.stop()
        traffic_wall_s = time.perf_counter() - t_run0

        failed, parity_max, answered = 0, 0.0, 0
        for i, fut in enumerate(futures):
            try:
                got = fut.result(timeout=5)
            except Exception:
                failed += 1
                continue
            answered += 1
            # pre-flip answers match A or B (a request admitted before
            # the flip may dispatch after it) — only definitely-post-flip
            # submissions are held to new-model parity below
            d_a = float(np.max(np.abs(got - exp_a[i]))) if len(got) else 0.0
            d_b = float(np.max(np.abs(got - exp_b[i]))) if len(got) else 0.0
            if min(d_a, d_b) > 0:
                failed += 1
        for i, fut in post_flip:
            if not fut.done() or fut.exception() is not None:
                continue
            got = fut.result(timeout=0)
            parity_max = max(
                parity_max, float(np.max(np.abs(got - exp_b[i])))
            )
        summary = engine.summary()
    finally:
        obs.reset()
        obs.disable()

    return {
        "n": num_requests * rows_per_req,
        "num_requests": num_requests,
        "rows_per_request": rows_per_req,
        "offered_qps": qps,
        "swap": swap,
        "answered": answered,
        "failed_requests": failed,
        "shed": int(stats.shed),
        "post_flip_requests": len(post_flip),
        "post_swap_parity_max_abs": parity_max,
        "traffic_compiles": summary["compiles"].get("backend_compiles"),
        "swap_build_compiles": summary["swap_build_compiles"],
        "e2e": stats.e2e_percentiles(),
        "examples_per_sec": round(
            answered * rows_per_req / max(traffic_wall_s, 1e-9), 2
        ),
    }


# ---------------------------------------------------------------------------
# Config: the daily warm-start retrain scenario (ISSUE 17). Day 0 trains
# a GLMix random-effect model OUT-OF-CORE (the double-buffered streaming
# pipeline, game/streaming.py) and saves a sequence-numbered model
# snapshot; day 1 streams a ~1/8-size delta over a subset of entities and
# warm-starts from the snapshot — touched entities retrain, every other
# entity's model carries over bit-exact. QUALITY_BANDS: warm retrain
# >= 3x faster than the cold fit (steady sweep walls — the compile bill
# is reported separately so a cold-cache builder doesn't poison the
# ratio), H2D overlap fraction >= 0.5 from the stream stage waterfall,
# zero steady-state compiles, carryover bit-exact.
# ---------------------------------------------------------------------------


def config_glmix_daily_retrain(peak_flops, scale):
    del peak_flops
    import tempfile

    import numpy as np

    from photon_tpu import obs
    from photon_tpu.game.checkpoint import ModelCheckpointStore
    from photon_tpu.game.config import RandomEffectCoordinateConfig
    from photon_tpu.game.data import CSRMatrix, GameData
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import (
        GLMProblemConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    n, users, d_re, chunk_rows = _pick(
        scale,
        (4000, 160, 6, 256),
        (60000, 2000, 16, 2048),
        (500000, 20000, 32, 8192),
    )
    n_delta = n // 8
    descent_iterations = 3
    # structure (ids, day split) is seed-stable so the touched-entity set
    # is reproducible; feature/label VALUES carry run entropy like every
    # other config, so the numeric work cannot be a replay
    rng = np.random.default_rng(17)
    value_entropy = int(time.time_ns() % (2**32))
    vrng = np.random.default_rng(value_entropy)

    def day_data(num_rows, id_pool):
        ids = np.asarray(id_pool)[
            _zipf_ids(rng, num_rows, len(id_pool))
        ]
        return GameData.build(
            labels=vrng.normal(size=num_rows),
            feature_shards={
                "s_user": CSRMatrix.from_dense(
                    vrng.normal(size=(num_rows, d_re))
                )
            },
            id_tags={"userId": [f"u{i}" for i in ids]},
        )

    def make_est():
        opt = GLMProblemConfig(
            task=TaskType.LINEAR_REGRESSION,
            regularization=RegularizationContext(RegularizationType.L2),
            optimizer_config=OptimizerConfig(max_iterations=6),
        )
        return GameEstimator(
            task=TaskType.LINEAR_REGRESSION,
            coordinate_configs={
                "per-user": RandomEffectCoordinateConfig(
                    random_effect_type="userId",
                    feature_shard="s_user",
                    optimization=opt,
                    regularization_weights=(1.0,),
                )
            },
            update_sequence=["per-user"],
            descent_iterations=descent_iterations,
        )

    def steady_sweeps(tracker):
        rows = [r for r in tracker if "sweep_seconds" in r]
        steady = [r for r in rows if r.get("iteration", 0) >= 1] or rows
        return (
            sum(r["sweep_seconds"] for r in steady),
            sum(r.get("compiles", 0) for r in steady),
        )

    def coef_map(re_model):
        vocab = np.asarray(re_model.vocab)
        return {
            str(vocab[i]): np.asarray(w)
            for i, w in enumerate(re_model.dense_coefficient_lookup())
            if w is not None
        }

    data0 = day_data(n, np.arange(users))
    # the delta day touches a strict subset of day-0 entities — the
    # carryover contract is measurable only if some entities are NOT in
    # today's data
    # 1/16 of the entities: at smoke scale the steady sweep wall is
    # per-chunk overhead-dominated and chunks scale with entities, so
    # the entity ratio — not the row ratio — is what keeps the measured
    # warm speedup comfortably above the 3x band on a contended runner
    touched_pool = rng.choice(users, size=max(2, users // 16), replace=False)
    data1 = day_data(n_delta, touched_pool)

    ckpt_dir = tempfile.mkdtemp(prefix="bench-daily-retrain-ckpt-")
    obs.reset()
    obs.enable()
    series_flusher = _start_series_flusher("glmix_daily_retrain")

    # day 0: the cold out-of-core fit, snapshot saved as seq 0
    est0 = make_est()
    t0 = time.perf_counter()
    res0 = est0.fit(data0, stream=chunk_rows, model_checkpoint_dir=ckpt_dir)[0]
    cold_wall = time.perf_counter() - t0
    stream0 = (est0.last_fit_stats or {}).get("stream") or {}
    cold_steady_s, cold_steady_compiles = steady_sweeps(res0.tracker)

    # day 1: the warm-start delta retrain against the same snapshot dir
    est1 = make_est()
    t0 = time.perf_counter()
    res1 = est1.fit(
        data1, stream=chunk_rows, warm_start=ckpt_dir,
        model_checkpoint_dir=ckpt_dir,
    )[0]
    warm_wall = time.perf_counter() - t0
    stream1 = (est1.last_fit_stats or {}).get("stream") or {}
    warm_steady_s, warm_steady_compiles = steady_sweeps(res1.tracker)

    # carryover audit: untouched entities bit-exact, touched retrained
    m0 = coef_map(res0.model.coordinates["per-user"])
    m1 = coef_map(res1.model.coordinates["per-user"])
    touched_keys = set(np.unique(np.asarray(data1.id_tags["userId"])))
    untouched = set(m0) - touched_keys
    carry_exact = bool(m0) and set(m0) <= set(m1) and all(
        np.array_equal(m0[k], m1[k]) for k in untouched
    )
    retrained = sum(
        1
        for k in touched_keys
        if k in m0 and not np.array_equal(m0[k], m1[k])
    )
    loaded = ModelCheckpointStore(ckpt_dir).load_latest()
    final_seq = loaded[1] if loaded is not None else None

    obs_dir = os.environ.get("PHOTON_OBS_DIR", "bench_obs")
    series_path = _stop_series_flusher(series_flusher)
    paths = obs.export_artifacts(
        obs_dir,
        prefix="glmix_daily_retrain.",
        meta={"config": "glmix_daily_retrain", "n": n},
    )
    obs.disable()
    obs.reset()

    return {
        "n": n,
        "n_delta": n_delta,
        "num_entities": users,
        "d_re": d_re,
        "chunk_rows": chunk_rows,
        "descent_iterations": descent_iterations,
        "value_entropy": value_entropy,
        # the cold streaming fit's pipeline report (stage waterfall, H2D
        # overlap split, ledger-verified residency) — the banded row
        "stream": stream0,
        "stream_warm": stream1,
        "stream_steady_compiles": cold_steady_compiles + warm_steady_compiles,
        "fit_wall_s": round(cold_wall, 3),
        "steady_sweep_s": round(cold_steady_s, 4),
        "examples_per_sec": round(
            n * max(descent_iterations - 1, 1) / cold_steady_s, 1
        )
        if cold_steady_s > 0
        else None,
        "retrain": {
            "warm_wall_s": round(warm_wall, 3),
            "warm_steady_sweep_s": round(warm_steady_s, 4),
            # the banded ratio: steady sweep walls, compile-free on both
            # sides (zero-steady-compile gated above) — at 1/8 data over
            # 1/4 entities a healthy warm day runs far more than 3x
            # faster than the cold fit
            "warm_speedup": round(cold_steady_s / warm_steady_s, 2)
            if warm_steady_s > 0
            else None,
            "wall_ratio": round(cold_wall / warm_wall, 2)
            if warm_wall > 0
            else None,
            "touched_entities": len(touched_keys),
            "retrained_entities": retrained,
            "untouched_entities": len(untouched),
            "carryover_bit_exact": carry_exact,
            "snapshot_seq": final_seq,
        },
        "obs": {
            "trace_path": paths.get("trace"),
            "metrics_path": paths.get("metrics"),
            "memory_path": paths.get("memory"),
            "series_path": series_path,
        },
    }


CONFIG_FNS = {
    "a1a_logistic_lbfgs": config_a1a,
    "linear_tron": config_tron,
    "sparse_poisson_owlqn": config_sparse_poisson,
    "glmix_game_estimator": config_glmix_estimator,
    "game_ctr_scale": config_game_ctr_scale,
    "game_scoring_stream": config_scoring_stream,
    "game_scoring_tail": config_scoring_tail,
    "game_serving_swap": config_game_serving_swap,
    "glmix_daily_retrain": config_glmix_daily_retrain,
}


def run_worker(name: str) -> None:
    t0 = time.perf_counter()
    from photon_tpu.util import compile_watch

    compile_watch.install()  # before backend init: count every compile
    platform, device_kind = _init_backend()
    scale = "smoke" if SMOKE else ("tpu" if platform == "tpu" else "cpu")
    _log(f"[bench:{name}] backend={platform} kind={device_kind} scale={scale}")
    peak_flops, peak_dtype = _peak_for(device_kind, platform)
    detail = CONFIG_FNS[name](peak_flops, scale)
    detail["metric_version"] = METRIC_VERSION
    detail["backend"] = platform
    detail["device_kind"] = device_kind
    detail["scale"] = scale
    detail["peak_flops_assumed"] = peak_flops
    detail["peak_flops_dtype"] = peak_dtype
    detail["worker_wall_s"] = round(time.perf_counter() - t0, 1)
    print("BENCHCFG_JSON: " + json.dumps({"config": name, "detail": detail}),
          flush=True)


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------


def _emit(results: dict) -> None:
    """Print the cumulative result line and mirror it to BENCH_partial.json."""
    configs = results["configs"]
    headline = configs.get("glmix_game_estimator", {}).get("examples_per_sec")
    if headline is None:  # fall back to any config that produced a number
        for name, _, _ in [(n, t, a) for n, t, a in CONFIG_PLAN]:
            if configs.get(name, {}).get("examples_per_sec") is not None:
                headline = configs[name]["examples_per_sec"]
                break
    # the headline must carry its backend/scale: a CPU-fallback run uses
    # reduced shapes and is NOT comparable to the TPU workload
    headline_name = next(
        (
            name
            for name, _, _ in CONFIG_PLAN
            if configs.get(name, {}).get("examples_per_sec") == headline
        ),
        None,
    )
    headline_cfg = configs.get(headline_name, {}) if headline_name else {}
    # per-config modeled Spark rates from recorded shapes + eval counters
    for name, _, _ in CONFIG_PLAN:
        cfg = configs.get(name)
        if cfg and "error" not in cfg:
            model = _spark_model_for(name, cfg)
            if model is not None:
                cfg["spark_model"] = model
    headline_model = headline_cfg.get("spark_model")
    vs_baseline = None
    if (
        headline
        and headline_cfg.get("scale") == "tpu"
        and headline_model is not None
    ):
        vs_baseline = round(
            headline
            / headline_model["modeled_examples_per_sec_per_executor"],
            2,
        )
    payload = {
        "metric": "GAME GLMix CD sweep throughput via GameEstimator.fit "
        "(FE + skewed per-user RE)",
        "metric_version": METRIC_VERSION,
        "value": headline,
        "unit": "examples/sec/chip",
        "backend": headline_cfg.get("backend"),
        "scale": headline_cfg.get("scale"),
        "vs_baseline": vs_baseline,
        "vs_baseline_unit": "Spark executors replaced per chip (lower "
        "bound; model constants favor Spark)",
        "vs_baseline_basis": VS_BASELINE_BASIS,
        **results,
    }
    line = json.dumps(payload)
    print(line, flush=True)
    try:
        with open(PARTIAL_PATH, "w") as f:
            f.write(line + "\n")
    except OSError as e:
        _log(f"[bench] could not write {PARTIAL_PATH}: {e}")


def run_orchestrator() -> int:
    """Every config on the chip, or nothing: a run that finds no chip
    returns 1 and publishes no row (one config runs on the CPU with
    ``BENCH_SMOKE=1 python bench.py --config NAME``)."""
    t_start = time.perf_counter()
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        _log("[bench] JAX_PLATFORMS=cpu: no chip to measure; nothing published")
        return 1
    if _probe_tpu() is None:
        _log("[bench] no TPU found; nothing published")
        return 1

    results: dict = {"backend_requested": "tpu", "configs": {},
                     "errors": {}}
    any_ok = False
    for name, timeout_s, attempts in CONFIG_PLAN:
        ok = False
        for attempt in range(attempts):
            _log(
                f"[bench] === config {name} attempt "
                f"{attempt + 1}/{attempts} "
                f"(timeout {timeout_s}s) ==="
            )
            t0 = time.perf_counter()
            detail, err = launch_config_worker(name, timeout_s, env)
            if detail is not None and detail.get("backend") != "tpu":
                err = f"ran on {detail.get('backend')!r}, not on the chip"
                detail = None
            if detail is not None:
                # quality gate: a throughput number from a garbage model
                # must fail the config, not publish (VERDICT r5 next #6).
                # Retries are allowed — a borderline band trip can be
                # draw noise; the rejected row is kept for debugging.
                violations = check_quality_bands(name, detail)
                if violations:
                    detail["band_violations"] = violations
                    results.setdefault("rejected", {})[name] = detail
                    err = f"quality band violated: {violations}"
                    detail = None
            if detail is not None:
                results["configs"][name] = detail
                ok = True
                any_ok = True
                _log(
                    f"[bench] config {name} ok in "
                    f"{time.perf_counter() - t0:.0f}s"
                )
                break
            _log(f"[bench] config {name} failed: {err}")
            results["errors"][name] = err
            if attempt + 1 < attempts:
                wait = 15 * (attempt + 1)
                _log(f"[bench] retrying {name} in {wait}s")
                time.sleep(wait)
        if ok and name in results["errors"]:
            del results["errors"][name]
        results["total_wall_s"] = round(time.perf_counter() - t_start, 1)
        _emit(results)  # flush after EVERY config — a later crash loses nothing

    return 0 if any_ok else 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(CONFIG_FNS), default=None)
    args = ap.parse_args()
    if args.config:
        run_worker(args.config)
    else:
        sys.exit(run_orchestrator())


if __name__ == "__main__":
    main()
