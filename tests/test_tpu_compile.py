"""The main path's device programs, compiled by the TPU's own compiler at
the shapes ``chip_smoke.py`` runs — with no chip attached.

The TPU compiler is installed in the sandbox and compiles for a chip that
is DESCRIBED (``v5e:2x2``), so what it refuses here it would refuse on
the machine with the chip: misaligned Pallas blocks, programs over the
16 GB of device memory, unsupported dtypes. Nothing runs, so these tests
say nothing about results or times.

``jax.default_backend()`` still reads ``cpu`` in such a compile, so the
whole module runs under one ``target.compiling_for("tpu")``: the layout is
built, the gathers fetch rows and the sweeps donate, as on the chip.
Nothing here RUNS a sweep or score program (XLA:CPU corrupts donated
buffers); a test that does belongs in another file.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU library, pytest-xdist workers
all import every test file, and only the worker that is handed this file
may touch it. Keep every such compile in THIS file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.game.data import (
    CSRMatrix,
    GameData,
    build_random_effect_dataset,
)
from photon_tpu.ops import sparse_windows
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from photon_tpu.util import target

#: the smoke's widths and scale (chip_smoke.py; bench ``game_ctr_scale``)
ROWS = chip_smoke.ROWS
FE_DIM = chip_smoke.FE_DIM
RE_DIM = chip_smoke.RE_DIM
#: one device holds 16 GB; a program whose arguments + temporaries pass
#: this cannot run next to anything else
HBM_BYTES = 16 * (1 << 30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _as_on_the_chip():
    """Programs for a TPU (module docstring), f32 like a run on the chip
    (conftest turns x64 on for the CPU suite), and no persistent cache: a
    compile for a described chip is written to it but cannot be read back
    without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with target.compiling_for("tpu"):
        yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def deployment():
    """The smoke's training rows as a GameData (no avro round trip): the
    FE shard carries the intercept as its last column, like the reader."""
    d = chip_smoke.generate(0, ROWS, chip_smoke.USERS, chip_smoke.ITEMS)
    n = ROWS
    k = chip_smoke.FE_NNZ
    cols = np.concatenate(
        [d["cols"][:n], np.full((n, 1), FE_DIM - 1, np.int64)], axis=1
    )
    vals = np.concatenate([d["vals"][:n], np.ones((n, 1))], axis=1)
    dense_indptr = np.arange(n + 1, dtype=np.int64) * RE_DIM
    dense_cols = np.tile(np.arange(RE_DIM, dtype=np.int32), n)
    return GameData.build(
        labels=d["labels"][:n],
        feature_shards={
            "global": CSRMatrix(
                indptr=np.arange(n + 1, dtype=np.int64) * k,
                indices=cols.reshape(-1).astype(np.int32),
                values=vals.reshape(-1),
                num_cols=FE_DIM,
            ),
            "per_user": CSRMatrix(
                indptr=dense_indptr,
                indices=dense_cols,
                values=d["xu"][:n].reshape(-1),
                num_cols=RE_DIM,
            ),
        },
        id_tags={"userId": [f"u{i}" for i in d["user"][:n]]},
    )


def _opt(max_iterations):
    return GLMProblemConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=max_iterations),
    )


def _on(tree, sharding):
    """The tree's arrays as shapes placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _fits(compiled):
    m = compiled.memory_analysis()
    need = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert need < HBM_BYTES, f"program needs {need} bytes of device memory"
    return m


@pytest.fixture(scope="module")
def fe_coordinate(deployment):
    """With the window layout: the TPU branch of ``maybe_build_windows``."""
    coord = FixedEffectCoordinate.build(
        deployment,
        FixedEffectCoordinateConfig(
            feature_shard="global",
            optimization=_opt(10),
            regularization_weights=(1.0,),
        ),
    )
    assert coord.batch.windows is not None
    return coord


def test_fe_sweep_compiles_for_v5e(fe_coordinate, one_chip):
    coord = fe_coordinate
    n = coord.batch.labels.shape[0]
    row = jax.ShapeDtypeStruct((n,), coord.dtype, sharding=one_chip)
    compiled = (
        type(coord)
        ._active_sweep_jit(None)  # the platform's: donating, as on the chip
        .lower(
            coord,
            _on(coord.batch, one_chip),
            _on(coord._norm_args(), one_chip),
            row,
            row,
            _on(coord._state_sds(), one_chip),
            _on(coord._scalar_sds(), one_chip),
        )
        .compile()
    )
    m = _fits(compiled)
    # total/score/state are donated on the chip: their buffers alias
    assert m.alias_size_in_bytes >= 2 * n * 4
    assert coord.batch.windows.instance_len == 4096
    assert coord.batch.windows.window == 128
    text = compiled.as_text()
    assert "photon.rmatvec.prefix" in text and "photon.gather.fetch" in text


def test_re_bucket_sweep_compiles_for_v5e(deployment, one_chip):
    """The fused RE sweep over ONE bucket of the per-user coordinate: the
    cap-sized one ([E, 128, 16], ~1.5 s). The whole coordinate is the same
    program over seven buckets and compiles in ~90 s here, nearly all of
    it in the [50407, 1, 16] singleton bucket (PERF.md, Open questions) —
    too long for a test every session runs."""
    cfg = RandomEffectCoordinateConfig(
        random_effect_type="userId",
        feature_shard="per_user",
        optimization=_opt(5),
        regularization_weights=(1.0,),
        active_data_upper_bound=chip_smoke.USER_CAP,
    )
    ds = build_random_effect_dataset(deployment, cfg, seed=0)
    assert sum(b.num_entities for b in ds.buckets) == chip_smoke.USERS
    # the score block is the whole of sample order whatever the buckets:
    # a coordinate of the one bucket, over every sample
    ds.buckets = [max(ds.buckets, key=lambda b: b.features.shape[1])]
    coord = RandomEffectCoordinate.build(deployment, ds, cfg)
    assert coord.device_buckets[0].features.shape[1:] == (
        chip_smoke.USER_CAP, RE_DIM,
    )
    row = jax.ShapeDtypeStruct(
        (coord.num_samples,), coord.dtype, sharding=one_chip
    )
    compiled = (
        type(coord)
        ._active_sweep_jit(True)
        .lower(
            coord,
            _on(coord._train_args(), one_chip),
            _on(coord._score_args(), one_chip),
            row,
            row,
            _on(coord._state_sds_list(), one_chip),
            coord._score_plan(),
            _on(coord._scalar_sds(), one_chip),
        )
        .compile()
    )
    _fits(compiled)


def test_scorer_batch_program_compiles_for_v5e(one_chip):
    """The fused score program of a GLMix model at the smoke's widths:
    FE d = 2^17 through a 32-wide ELL block (24 nnz snapped to its
    power-of-two level), per-user and per-item tables of d = 16."""
    from photon_tpu.game.scoring import GameScorer, _FixedSpec, _RandomSpec

    # the program alone: a scorer's static specs without a model behind it
    scorer = object.__new__(GameScorer)
    scorer._fixed = [_FixedSpec(cid="global", shard="global")]
    scorer._random, scorer._mf = [], []
    params = {"fe": {"global": np.zeros(FE_DIM, np.float32)}, "re": {}, "mf": {}}
    for cid, shard, tag, e_n in (
        ("per-user", "per_user", "userId", chip_smoke.USERS),
        ("per-item", "per_item", "itemId", chip_smoke.ITEMS),
    ):
        scorer._random.append(
            _RandomSpec(
                cid=cid, shard=shard, tag=tag, projected=False,
                num_entities=e_n,
            )
        )
        params["re"][cid] = {
            "coef": np.zeros((e_n + 1, RE_DIM), np.float32),
            "col": np.zeros((e_n + 1, RE_DIM), np.int32),
        }
    b = chip_smoke.SCORE_BATCH_ROWS
    batch = {
        "offsets": np.zeros((b,), np.float32),
        "ell": {
            "global": (
                np.zeros((b, 32), np.int32),
                np.zeros((b, 32), np.float32),
            )
        },
        "dense": {
            "per_user": np.zeros((b, RE_DIM + 1), np.float32),
            "per_item": np.zeros((b, RE_DIM + 1), np.float32),
        },
        "eidx": {
            "per-user": np.zeros((b,), np.int32),
            "per-item": np.zeros((b,), np.int32),
        },
        "mf": {},
    }
    compiled = (
        jax.jit(scorer._score_fn, donate_argnums=(1,))
        .lower(_on(params, one_chip), _on(batch, one_chip))
        .compile()
    )
    _fits(compiled)


def test_chunked_gather_compiles_for_v5e(fe_coordinate, one_chip):
    """The lane gather at the FE window stream's size (every slot of the
    layout reads one element of an [N] per-row vector)."""
    from photon_tpu.ops.gather import chunked_take

    w = fe_coordinate.batch.windows
    table = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct(w.rows.shape, jnp.int32, sharding=one_chip)
    _fits(jax.jit(chunked_take).lower(table, idx).compile())


def _rmatvec_compiled(fn, fe_coordinate, one_chip):
    w = _on(fe_coordinate.batch.windows, one_chip)
    per_row = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)
    return (
        jax.jit(fn, static_argnums=2).lower(w, per_row, FE_DIM).compile()
    )


def test_prefix_rmatvec_compiles_for_v5e(fe_coordinate, one_chip):
    _fits(
        _rmatvec_compiled(
            sparse_windows.rmatvec_windows_prefix, fe_coordinate, one_chip
        )
    )


# --- the sparse cell's two passes, from shapes alone ------------------------

#: ``benchmarks/configs/sparse_poisson.json`` and the layout its structure
#: builds: 58 384 instances of 4096 slots over windows of 128 columns,
#: before the build pads the count to the backward pass's consumer block
#: (4 segments of 32 instances: 58 496)
CELL_N, CELL_K, CELL_D = 1 << 22, 56, 1 << 20
CELL_INSTANCES, CELL_LENGTH, CELL_WINDOW = 58384, 4096, 128


@pytest.fixture(scope="module")
def cell_passes(one_chip):
    """(name, compiled, slots) of the forward and the backward pass at the
    cell's shapes, as a TPU takes them; no data, seconds each."""
    from photon_tpu.ops.objective import matvec
    from photon_tpu.types import SparseBatch

    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    n, k, d = CELL_N, CELL_K, CELL_D
    batch = SparseBatch(
        indices=sds((n, k), jnp.int32), values=sds((n, k)),
        labels=sds((n,)), offsets=sds((n,)), weights=sds((n,)),
        windows=None,
    )
    forward = jax.jit(matvec).lower(batch, sds((d,))).compile()
    w_inst = CELL_INSTANCES
    w_inst += (-w_inst) % sparse_windows.instance_multiple(
        w_inst, CELL_LENGTH, 4
    )
    windows = sparse_windows.ColumnWindows(
        rows=sds((w_inst, CELL_LENGTH), jnp.int32),
        lcols=sds((w_inst, CELL_LENGTH), jnp.int32),
        vals=sds((w_inst, CELL_LENGTH)),
        inst2win=sds((w_inst,), jnp.int32),
        iota=sds((CELL_WINDOW,), jnp.int32),
        bounds=sds((w_inst, CELL_WINDOW + 1), jnp.int32),
    )
    backward = (
        jax.jit(sparse_windows.rmatvec_windows_prefix, static_argnums=2)
        .lower(windows, sds((n,)), d)
        .compile()
    )
    return {
        "forward": (forward, n * k),
        "backward": (backward, w_inst * CELL_LENGTH),
    }


def _row_fetches(text):
    """(table type, fetched block type) of every fusion that gathers
    128-lane rows, memory spaces and all."""
    import re

    out = []
    for m in re.finditer(
        r"\n%fused_computation[^\s]* \(.*?\) -> [^\n]*\{\n(.*?)\n\}", text, re.S
    ):
        lines = m.group(1).splitlines()
        if not any("slice_sizes={1,128}" in ln for ln in lines):
            continue
        table = next(ln for ln in lines if " parameter(0)" in ln)
        root = next(ln for ln in lines if ln.strip().startswith("ROOT"))
        out.append(
            (table.split(" = ")[1].split(" ")[0], root.split(" = ")[1].split(" ")[0])
        )
    return out


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_cell_pass_keeps_fetched_rows_in_fast_memory(cell_passes, which):
    """What PR 30 is for: the table AND the segment's block of fetched rows
    are assigned to memory space 1 (``S(1)``), so neither the fetch nor the
    select goes through HBM."""
    from photon_tpu.ops.gather import _SEG_BYTES

    compiled, _ = cell_passes[which]
    fetches = _row_fetches(compiled.as_text())
    assert fetches, "no 128-lane row fetch in the program"
    slots = lambda f: int(f[1].split("[")[1].split(",")[0])  # noqa: E731
    # the loop's fetch (a ragged end's runs once, on a smaller block)
    table, block = max(fetches, key=slots)
    assert "S(1)" in table, f"the table is read from HBM: {table}"
    assert "S(1)" in block, f"the fetched rows go to HBM: {block}"
    assert _SEG_BYTES // 2 < slots((table, block)) * 512 <= _SEG_BYTES
    assert all("S(1)" in b for _, b in fetches), fetches


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_cell_pass_temporaries_and_relayouts(cell_passes, which):
    """Under 1.5 GB of temporaries (4.30 and 4.17 GB before the segment
    loop), and under ``photon.gather`` no copy, reshape, pad, slice or
    dynamic-update-slice of a whole stream."""
    import math

    from photon_tpu.analysis import hlo

    compiled, slots = cell_passes[which]
    _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    paths = hlo.instruction_scope_paths(text)
    relayouts = []
    for ins in hlo.parse_instructions(text).values():
        if "photon.gather" not in paths.get(ins.name, ()):
            continue
        if ins.opcode not in (
            "copy", "reshape", "pad", "slice", "dynamic-update-slice"
        ):
            continue
        dims = ins.shape.split("[")[1].split("]")[0]
        if math.prod(int(x) for x in dims.split(",") if x) >= slots:
            relayouts.append((ins.name, ins.opcode, ins.shape))
    assert not relayouts, relayouts
    under = {p for p in paths.values() if "photon.gather" in p}
    assert under and all(
        p[-1] in ("photon.gather.fetch", "photon.gather.select") for p in under
    ), sorted(under)


def test_cell_backward_pass_scans_with_the_instances_on_the_lanes(cell_passes):
    """What PR 35 is for: the prefix sums under ``photon.rmatvec.prefix``
    scan a block of at least 128 instances laid out with the instance axis
    minor (``{0,1,2}``: the instances on the lanes, the scanned 128 down
    the major axis, plain vector adds). On a segment's 32 instances the
    same line read ``f32[32,32,128]{2,1,0}``: a scan across the lanes."""
    import re

    from photon_tpu.analysis import hlo

    compiled, slots = cell_passes["backward"]
    assert slots == 58496 * CELL_LENGTH
    text = compiled.as_text()
    paths = hlo.instruction_scope_paths(text)
    scans = [
        ins.shape
        for ins in hlo.parse_instructions(text).values()
        if ins.opcode == "reduce-window"
        and "photon.rmatvec.prefix" in paths.get(ins.name, ())
    ]
    # the scan inside chunks of 128 slots, and the one over the chunks' sums
    assert sorted(s.count(",", 0, s.index("]")) for s in scans) == [1, 2], scans
    for shape in scans:
        m = re.match(r"f32\[(\d+),([\d,]+)\]\{([\d,]+):", shape)
        assert m, shape
        assert int(m.group(1)) >= 128, f"a block under 128 instances: {shape}"
        assert m.group(3).startswith("0,"), f"the instances are not minor: {shape}"
        assert "S(1)" in shape, f"the scanned block lies in HBM: {shape}"
    (main,) = (s for s in scans if s.count(",", 0, s.index("]")) == 2)
    assert main.startswith(f"f32[128,{CELL_LENGTH // 128},128]{{0,1,2:"), main


def test_cell_forward_pass_has_no_second_level(cell_passes):
    """The forward pass is the one-level loop it was (its text is the
    parent's byte for byte but for metadata: PERF.md §6, PR 35): one loop
    that stacks the plan's 1820 segments of margins, nothing conditional in
    it."""
    from photon_tpu.ops.gather import segment_plan

    text = cell_passes["forward"][0].as_text()
    assert "conditional(" not in text
    plan = segment_plan(CELL_N, CELL_K, 4, 128)
    assert text.count(" while(") == 1
    assert f"f32[{plan.segments},{plan.per}]" in text  # the stacked margins
    block = plan.per * CELL_K
    assert f"f32[{block},128]" in text


def _shapes_only_re_sweep(one_chip, buckets, n, optimizer_config):
    """The fused RE sweep over ``buckets`` [(entities, rows, d, kept rows)]
    of a coordinate of ``n`` samples, compiled from shapes alone: no data
    is built. The score blocks are what ``RandomEffectCoordinate.build``
    would place: one ``[n, d]`` block in sample order where the buckets
    share a width, else a block of its buckets' kept rows, with their
    positions, for each width. -> (the problem configuration, the compiled
    program)."""
    opt = GLMProblemConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=optimizer_config,
    )
    coord = RandomEffectCoordinate(
        config=RandomEffectCoordinateConfig(
            random_effect_type="e", feature_shard="e", optimization=opt,
            regularization_weights=(1.0,),
            active_data_upper_bound=max(rows for _, rows, _, _ in buckets),
        ),
        dataset=None, device_buckets=[],
        problem_config=opt.with_regularization_weight(1.0),
        num_samples=n, dtype=jnp.float32,
    )

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = tuple(
        (
            sds((entities, rows, d)), sds((entities, rows)), sds((entities, rows)),
            sds((entities, rows)), sds((entities, rows), jnp.int32),
        )
        for entities, rows, d, _ in buckets
    )
    widths = {}
    for i, (_, _, d, kept) in enumerate(buckets):
        widths.setdefault(d, []).append((i, kept))
    score_args, plan = [], []
    for d, members in widths.items():
        m = n if len(widths) == 1 else sum(kept for _, kept in members)
        positions = () if len(widths) == 1 else (sds((m,), jnp.int32),)
        score_args.append((sds((m, d)), sds((m,), jnp.int32)) + positions)
        plan.append(tuple(i for i, _ in members))
    compiled = (
        type(coord)
        ._active_sweep_jit(True)
        .lower(
            coord, blocks, tuple(score_args), sds((n,)), sds((n,)),
            [sds((entities, d)) for entities, _, d, _ in buckets],
            tuple(plan), sds(()),
        )
        .compile()
    )
    return opt, compiled


#: the row-heavy cell's random-effect solver (glmix_movielens.json)
ROW_HEAVY = OptimizerConfig(
    max_iterations=10, ls_max_iterations=8, tolerance=1e-7, num_corrections=10
)


def test_cell_single_row_bucket_sweep_fits_its_budget(one_chip):
    """The per-user coordinate's sweep over the ``glmix_ctr.sweeps`` cell's
    one-row bucket, [1 997 496, 1, 16] (the bucket the cell's structure seed
    gives at 2^22 rows and 2^21 users, PERF.md PR 31): the solve runs as a
    loop over entity chunks, and the program's temporaries stay under the
    2 GB the cell allows the whole coordinate (before PR 31: 59 KB an
    entity, 118 GB, and no chunk loop). Shapes only: no data is built."""
    import json
    import os

    from photon_tpu.game import coordinate as coordinate_mod

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", "glmix_ctr.json")) as f:
        cell = json.load(f)
    entities, rows, d = 1_997_496, 1, cell["random_effects"]["per_user"]["d"]
    opt, compiled = _shapes_only_re_sweep(
        one_chip, [(entities, rows, d, entities)], cell["features"]["n"],
        OptimizerConfig(
            max_iterations=cell["solver"]["re_max_iterations"],
            ls_max_iterations=cell["solver"]["re_ls_max_iterations"],
        ),
    )
    chunk = coordinate_mod.solve_chunk_entities(
        entities, rows, d, opt.optimizer_config
    )
    assert chunk % 1024 == 0 and 4 <= -(-entities // chunk) <= 32
    m = _fits(compiled)
    assert m.temp_size_in_bytes < 2 * (1 << 30), m.temp_size_in_bytes
    text = compiled.as_text()
    # the chunk loop sits under the bucket's scope, the solver under it
    assert "photon.re.solve/while/body/" in text
    assert "photon.re.chunk/vmap()/while/body/photon.lbfgs.linesearch" in text
    # PR 40: this bucket alone makes the coordinate's table 128 MB packed
    # (the cell's 2^21 + 1 entities: 134 MB), far over what stays in fast
    # memory: its rows keep the parent's plain gather, one block, no loop
    from photon_tpu.ops import gather

    assert gather.packed_table_bytes(entities + 1, d, 4) > gather._PACKED_TABLE_BYTES
    assert _rescore_gathers(compiled) == [f"1,{d}"]
    assert "photon.re.rescore/while" not in text


@pytest.mark.parametrize("rows,fewer,more", [(1024, 8192, 16384), (4096, 2048, 4096)])
def test_row_heavy_solve_temporaries_against_solve_entity_bytes(
    one_chip, monkeypatch, rows, fewer, more
):
    """``solve_entity_bytes`` read against the compiler's own report for
    [E, 1024, 16] and [E, 4096, 16] (PR 36): what an entity adds to the
    sweep program's temporaries between two entity counts past what fast
    memory absorbs (23.5 KB and 93.7 KB when written) is under the price
    and over half of it. Until PR 36 the price was seven times the
    report: the block was priced as if carried through the loops."""
    from photon_tpu.game import coordinate as coordinate_mod

    # no chunk loop: the whole bucket's temporaries are what is read
    monkeypatch.setattr(coordinate_mod, "RE_SOLVE_BYTES", 1 << 40)
    # few samples: the score block is [n, 16] (PR 37), and its rescoring's
    # temporaries are not what is read here
    n = 1 << 13
    temps = []
    for entities in (fewer, more):
        opt, compiled = _shapes_only_re_sweep(
            one_chip, [(entities, rows, 16, 1024)], n, ROW_HEAVY
        )
        temps.append(_fits(compiled).temp_size_in_bytes)
    grown = (temps[1] - temps[0]) / (more - fewer)
    priced = coordinate_mod.solve_entity_bytes(rows, 16, opt.optimizer_config)
    assert 0.5 * priced < grown <= priced, (grown, priced)


def _rescore_gathers(compiled):
    """The ``slice_sizes`` of every gather under ``photon.re.rescore``, the
    fused ones included."""
    import re

    text = compiled.as_text()
    return [
        m.group(1)
        for ln in text.splitlines()
        if " gather(" in ln and "photon.re.rescore" in ln
        for m in [re.search(r"slice_sizes=\{([\d,]+)\}", ln)]
        if m
    ]


@pytest.mark.parametrize("case,d", [("packed_segments", 16), ("plain_row_chunks", 24)])
def test_cell_row_heavy_bucket_rescoring(one_chip, case, d):
    """The per-movie coordinate's capped bucket of ``glmix_movielens.sweeps``,
    [751, 4096, 16] (the bucket the cell's structure seed gives), with the
    coordinate's score block, the whole of sample order, [2^23, 16]. Since
    PR 40 its 752-row table is packed eight entities to a lane row and the
    rows go through ``gather.segment_plan``'s 256 segments of 32 768 (four
    lane rows a row: the fetched one, its rows-on-lanes copy, room) under
    ``photon.re.rescore``, holding under 0.2 GB of temporaries (PR 37: two
    chunks of 2^22 rows, 4.3 GB). A width that does not divide 128 keeps
    the plain gather, and ``rescore_chunk_rows`` still cuts its 2^23 rows
    in two (``RE_RESCORE_BYTES``: 8.6 GB of lane-padded gather at once).
    The solve needs no chunk loop. Shapes only: no data is built."""
    from photon_tpu.game import coordinate as coordinate_mod
    from photon_tpu.ops import gather

    n = 1 << 23
    chunk = coordinate_mod.rescore_chunk_rows(n, d)
    assert chunk % 1024 == 0 and -(-n // chunk) == 2
    opt, compiled = _shapes_only_re_sweep(
        one_chip, [(751, 4096, d, 5_255_525)], n, ROW_HEAVY
    )
    assert coordinate_mod.solve_chunk_entities(751, 4096, d, opt.optimizer_config) == 751
    m = _fits(compiled)
    text = compiled.as_text()
    assert "photon.re.rescore/while/body/" in text
    assert "photon.re.chunk" not in text
    if case == "packed_segments":
        plan = gather.segment_plan(n, 4, 4, 1024)
        assert plan == (256, 32768, 0)
        assert f"f32[{plan.segments},{plan.per}]" in text  # the stacked scores
        assert m.temp_size_in_bytes < 0.2e9, m.temp_size_in_bytes
        assert _rescore_gathers(compiled) == ["1,128"]
    else:
        assert gather.packed_table_bytes(752, d, 4) == 0
        assert m.temp_size_in_bytes < chunk * coordinate_mod.rescore_row_bytes(d) * 1.1
        assert _rescore_gathers(compiled) == [f"1,{d}"]


def test_cell_per_user_rescoring_fetches_lane_rows_of_the_packed_table(one_chip):
    """What PR 40 is for: the per_user coordinate of ``glmix_movielens.sweeps``
    (two of its buckets, [1108, 1026, 16] and the other 64 428 entities:
    with the zero row a table of 65 537 rows, 4.2 MB packed where the
    compiler's lane-padded ``coefs[slot]`` read 33.6 MB, out of fast
    memory) and its [2^23, 16] block: the one gather under
    ``photon.re.rescore`` fetches 128-lane rows, table, fetched block and
    the block's rows-on-lanes copy in memory space 1, inside the segment
    loop, and the program's temporaries are under 1 GB (4.3 GB at PR 37)."""
    _, compiled = _shapes_only_re_sweep(
        one_chip, [(1108, 1026, 16, 1_200_000), (64428, 39, 16, 2_000_000)],
        1 << 23, ROW_HEAVY,
    )
    m = _fits(compiled)
    assert m.temp_size_in_bytes < 1e9, m.temp_size_in_bytes
    assert _rescore_gathers(compiled) == ["1,128"]
    text = compiled.as_text()
    assert "photon.re.rescore/while/body/" in text
    assert "photon.gather/photon.gather.fetch" in text
    assert "photon.gather/photon.gather.select" in text
    ((table, block),) = _row_fetches(text)
    assert table.startswith("f32[8193,128]") and "S(1)" in table, table
    assert block.startswith("f32[32768,128]") and "S(1)" in block, block
    relaid = [ln for ln in text.splitlines() if " copy(" in ln and "f32[32768,128]{0,1" in ln]
    assert relaid and all("S(1)" in ln.split(" copy(")[0] for ln in relaid), relaid


def _scatters_and_sorts(compiled):
    """(the ``scatter`` instructions under ``photon.re.rescore``, every
    ``sort`` instruction) of a compiled program."""
    from photon_tpu.analysis import hlo

    text = compiled.as_text()
    paths = hlo.instruction_scope_paths(text)
    instrs = hlo.parse_instructions(text).values()
    return (
        [i for i in instrs if i.opcode == "scatter"
         and "photon.re.rescore" in paths.get(i.name, ())],
        [i for i in instrs if i.opcode == "sort"],
    )


@pytest.mark.parametrize("cell,n,bucket", [
    # the largest per-user bucket of each GLMix cell, as its structure seed gives it
    ("glmix_movielens", 1 << 23, (1108, 1026, 16, 1_200_000)),
    ("glmix_ctr", 1 << 22, (647, 256, 16, 400_000)),
])
def test_one_width_rescoring_neither_sorts_nor_scatters(one_chip, cell, n, bucket):
    """What PR 37 is for. Both GLMix cells' coordinates have one bucket
    width, so their score block is sample order and ``jit_re_sweep`` holds
    no ``sort`` anywhere and no ``scatter`` under ``photon.re.rescore``
    (at the parent: a sort of the (position, score) pairs and a scatter of
    them one element at a time, a bucket). The residual fetch has its own
    scope inside the solve's."""
    _, compiled = _shapes_only_re_sweep(one_chip, [bucket], n, ROW_HEAVY)
    _fits(compiled)
    scatters, sorts = _scatters_and_sorts(compiled)
    assert not sorts, [i.name for i in sorts]
    assert not scatters, [i.name for i in scatters]
    text = compiled.as_text()
    assert " sort(" not in text
    assert "photon.re.solve/photon.re.fetch" in text


def test_two_width_rescoring_scatters_sorted_and_unique_without_a_sort(one_chip):
    """A coordinate whose buckets have two widths keeps a block a width
    and adds each at its ascending, distinct positions: one scatter a
    block that promises both, and still no sort."""
    _, compiled = _shapes_only_re_sweep(
        one_chip,
        [(1108, 1026, 16, 1_200_000), (4096, 64, 8, 300_000)],
        1 << 23, ROW_HEAVY,
    )
    _fits(compiled)
    scatters, sorts = _scatters_and_sorts(compiled)
    assert not sorts and " sort(" not in compiled.as_text()
    assert len(scatters) == 2, [i.name for i in scatters]
    for ins in scatters:
        assert "indices_are_sorted=true" in ins.attributes, ins.attributes
        assert "unique_indices=true" in ins.attributes, ins.attributes
