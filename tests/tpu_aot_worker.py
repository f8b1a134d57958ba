"""AOT-compile the repo's Pallas kernels, and the packed FM step, for a
device-less TPU v5e topology.

Run as a subprocess by tests/test_tpu_aot_compile.py (libtpu takes a lock
and is noisy): ``python tests/tpu_aot_worker.py CASE...`` prints one JSON
line per case. Every kernel is lowered with ``interpret=False`` at bench
geometry and handed to the real Mosaic/XLA TPU compiler, so a kernel that
only ever ran in interpret mode and does not fit VMEM fails HERE, without
chip time.
"""

import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from hivemall_tpu.ops import fm, pallas_hist, rows_pallas
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer

# the kernels under test ask the device policy; here the target is the
# topology, not this process's (CPU) backend
pallas_hist.pallas_interpret = lambda: False
rows_pallas.use_kernels_default = lambda: True

TOPO = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
DEV0 = SingleDeviceSharding(TOPO.devices[0])

K = 4                                   # the flagship's factors
LAMS = (0.01, 0.01, 0.01)


def _sds(shape, dtype, sharding=DEV0):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mesh_1x4():
    return Mesh(np.asarray(TOPO.devices).reshape(1, 4), ("dp", "tp"))


def _slab_gathers(text, slab):
    """{branches of the `cond`s around the gather, outermost first
    ("branch_0/branch_1"): shape of the operand read} for the step's
    forward gathers (`hm.gather`, a result of shape `slab`)."""
    out = {}
    for m in re.finditer(r"= %s\S* gather\(%%([\w.]+),.*?op_name=\"([^\"]*)\""
                         % re.escape(slab), text):
        operand, path = m.groups()
        if "/hm.gather/" not in path:
            continue
        branches = re.findall(r"branch_\d", path)
        out["/".join(branches) or "no_cond"] = re.search(
            r"%%%s = (\w+\[[\d,]*\])" % re.escape(operand), text).group(1)
    return out


def _compact_table_spaces(text, shape):
    """The memory spaces the compiled text gives the compact table of
    shape `shape` in its fill's loop (`S(1)`: the chip's fast memory; ""
    is HBM)."""
    line = next(line for line in text.splitlines() if " while(" in line
                and "/hm.gather/while" in line and shape + "{" in line)
    return set(re.findall(r"%s\{[^}]*?(S\(\d\))?\}" % re.escape(shape), line))


def _fitting_path(text):
    """The instructions of a compiled module that run when every
    `conditional` takes its LAST branch (`lax.cond`'s true one: a batch
    within the capacities), from ENTRY down through fusions, calls and
    loops."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    entry = re.search(r"\nENTRY %([\w.-]+) ", text).group(1)
    seen, todo, lines = set(), [entry], []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            lines.append(line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                todo.append(branches.group(1).split(",")[-1].strip(" %"))
            todo += re.findall(
                r"(?:calls|body|condition|to_apply|true_computation)=%"
                r"([\w.-]+)", line)
    return lines


def _assert_compact_gather(text, slab, compact, table):
    """`gather_rows` as compiled (PR 34): the distinct branch of its cond
    reads the slots (a result of shape `slab`) out of the `compact` table
    and never out of the whole `table`, which the other branch reads; and
    the compiler keeps the compact table in fast memory, which is what
    the gather's 1.9 ns a row against 10.2 rests on (at the ranking's
    282,624 rows FM's stays in HBM and the step LOSES 2.3 ms)."""
    assert _slab_gathers(text, slab) == {
        "branch_1": compact, "branch_0": table}, _slab_gathers(text, slab)
    spaces = _compact_table_spaces(text, compact)
    assert spaces == {"S(1)"}, f"the compact table lives in {spaces}"


def _assert_compact_scatter(text, n, W, scap):
    """The tail's scatter-add as compiled (PR 38): the first pass is
    `rows_pallas.scatter_rows`, whose sum [scap, W] the kernel holds in
    fast memory (memory space 1, `S(1)`, is what it reserves), and a
    batch within the capacities gathers no slab row through the
    permutation (the parent's sorted scatter read `g[perm]` out of HBM;
    only the dense tail of a batch over the ranking's capacity still
    does)."""
    lanes = -(-W // 128) * 128
    calls = re.findall(r"%%scatter_rows\S* = f32\[%d,8,%d\]\S* custom-call"
                       r"\(.*?\"memory_space\":\"1\",\"offset\":\"0\","
                       r"\"size\":\"(\d+)\"" % (scap // 8, W), text)
    assert calls and all(int(c) >= scap * lanes * 4 for c in calls), calls
    reads = [line for line in _fitting_path(text)
             if re.search(r"= f32\[%d,%d\]\S* gather\(" % (n, W), line)]
    assert not reads, f"the slab read through perm: {reads}"


def fm_minibatch_step():
    """ONE un-scanned make_fm_step_minibatch step at the geometry of the
    benchmark's cell fm_criteo.stream (-dims 2^26 -factors 5, B=32768,
    L=39, float32, unit values elided). The compiled program must hold no
    loop the compiler made: the compiler wrote the packed unpack's
    [B, L, P, Wf] reshape as two 128-trip loops over lanes, 43% of the
    step's device time (PERF.md, PR 24 / PR 25), and on the CPU that
    tier-1 runs on such a reshape costs nothing. The one `while` it holds
    since PR 34 is `gather_rows`' own: the fill of the compact table, a
    block a trip up to the batch's count of distinct rows."""
    K, B, L = 5, 32768, 39
    Wf, Pk = fm.fm_pack_geometry(K)
    Np = (1 << 26) // Pk
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    step = fm.make_fm_step_minibatch(get_loss("logloss"), opt, LAMS, K)
    table = _sds((Np, Pk * Wf), jnp.float32)
    compiled = step.lower(
        {"T": table, "w0": _sds((), jnp.float32)},
        {"T": {"gg": table}, "w0": {"gg": _sds((), jnp.float32)}},
        _sds((), jnp.float32), _sds((B, L), jnp.int32), None,
        _sds((B,), jnp.float32), _sds((B,), jnp.float32)
    ).compile()
    text = compiled.as_text()
    loops = re.findall(r" while\(.*?op_name=\"([^\"]*)\"", text)
    assert len(loops) == 2 and "/hm.gather/while" in loops[1] \
        and loops[0].endswith("/cond/branch_1_fun/while"), \
        f"while op(s) {loops}, expected the compact table's fill and the " \
        "tail's passes"
    # the distinct-row tail (PR 28): one cond between it and the dense
    # tail, the donated tables passing through both in place (the row
    # kernel aliases them: ONE Mosaic kernel since PR 30; since PR 38 the
    # compact scatter and the row kernel in a loop of passes, one trip for
    # a batch within scatter_cap), and no more
    # temporaries than the dense tail's G beside the gradient slab (2.80 GB
    # read here in PRs 30 and 34: the gather's compact table, 101 MB, and
    # the compact gradient live where the dense branch's G would). In
    # front (PR 34): a cond that lists the distinct rows only for a batch
    # within the capacity, and the gather's own, the slab leaving either
    # of its branches without a copy
    cap = fm.tail_cap(B * L, Np)
    assert cap, "the cell's shape must offer the tail"
    gcap, W = fm.gather_cap(cap, Pk * Wf, 4), Pk * Wf
    conds = text.count(" conditional(")
    assert conds == 3, \
        f"{conds} conditional op(s), expected the list, gather and tail"
    _assert_compact_gather(text, f"f32[{L},{B},{W}]", f"f32[{gcap},{W}]",
                           f"f32[{Np},{W}]")
    _assert_compact_scatter(text, B * L, W, fm.scatter_cap(cap, W))
    slab_copies = re.findall(r"= f32\[(?:%d,%d|%d),%d\]\S* copy\("
                             % (L, B, L * B, Pk * Wf), text)
    assert not slab_copies, f"{len(slab_copies)} copies of the slab"
    kernels = text.count("tpu_custom_call")
    assert kernels == 2, \
        f"{kernels} Mosaic kernels, expected scatter_rows and update_rows"
    copies = re.findall(r"= f32\[%d,%d\]\S* copy\(" % (Np, Pk * Wf), text)
    assert not copies, f"{len(copies)} copies of a whole table"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2.85e9, f"{temp / 1e9:.2f} GB of temporaries"


def ffm_joint_megastep():
    """The megastep (two steps: a scan's body compiles once) of
    make_ffm_step_fused at the geometry of the benchmark's cell
    ffm_criteo_joint.stream (-dims 2^28 -fields 39 -factors 4 -halffloat:
    a [4194304, 164] bfloat16 table and its float32 AdaGrad state,
    B=32768, L=39, fieldmajor, unit values elided), its tail through
    rows_update (PR 32). The megastep and not the one step: the chip keeps a
    164-lane table transposed (`{0,1}`), every program relayouts it on the
    way in and out, and the cell pays that once a dispatch.

    Mosaic's verdict on this shape was learned here: it compiles no row
    copy of a 164-lane array and no bfloat16 pair (ops/rows_pallas.py has
    its words), so the distinct rows go through XLA's gather and scatter
    in blocks (the compact sum through `scatter_rows` since PR 38: its
    rows are a VMEM array's, which Mosaic takes at 164 lanes): one
    `conditional` between the tails and, since PR 34, one
    around the list of distinct rows and one between the gathers (the
    distinct branch reads the slots out of the compact [cap, 164] table,
    kept in fast memory, the other out of the whole one), three
    `while` (the scan, the blocks' loop and the compact table's fill,
    whose trips follow the batch's count), no Mosaic kernel, the four
    relayouts of the tables at the megastep's two ends (the dense tail's
    program has the same four) and none inside the scan, and no more
    temporaries than the dense tail's program: 15.04 GB read against its
    15.05 (both hold the dense branch's G; the figure counts buffers the
    chip overlays, the cell peaks at 4.95 GB). Since PR 38 the compact
    gradient is summed by `rows_pallas.scatter_rows` (the one Mosaic
    kernel, in a loop of passes of scatter_cap rows with the blocks' loop
    inside), which reads the slab [B, 39, 164] as the backward leaves it
    after one transposing copy, so the slab lives into the tail's cond and
    each branch relayouts it once for itself; the dense branch gathers its
    rows through `perm` where they lie (a reshape there put a second slab
    beside the first: 16.78 GB, a review's finding): 15.07 GB read."""
    from hivemall_tpu.ops.scan import make_megastep
    Fj, B, L, ks = 39, 32768, 39, 2
    Mr, W = 1 << 22, Fj * K + 8
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    step = fm.make_ffm_step_fused(get_loss("logloss"), opt, LAMS, Fj, K,
                                  fieldmajor=True, unit_val=True)
    T, gg = _sds((Mr, W), jnp.bfloat16), _sds((Mr, W), jnp.float32)
    cap = fm.tail_cap(B * L, Mr, W, 2)
    assert cap, "the cell's shape must offer the tail"
    assert not rows_pallas.kernel_takes((T, gg))
    compiled = make_megastep(step.core).lower(
        {"T": T, "w0": _sds((), jnp.float32)},
        {"T": {"gg": gg}, "w0": {"gg": _sds((), jnp.float32)}},
        _sds((), jnp.float32), _sds((ks,), jnp.int32),
        _sds((ks, B, L), jnp.int32), None, _sds((ks, B), jnp.float32), None,
        None).compile()
    text = compiled.as_text()
    conds, loops = text.count(" conditional("), text.count(" while(")
    assert conds == 3, \
        f"{conds} conditional op(s), expected the list, gather and tail"
    # since PR 38 the tail's passes are a loop (one trip within
    # scatter_cap), the blocks' loop inside it
    assert loops == 4, f"{loops} while op(s), expected the scan, the " \
        "tail's passes, the blocks and the fill"
    _assert_compact_gather(
        text, f"bf16[{B},{L},{W}]",
        f"bf16[{fm.gather_cap(cap, W, 2)},{W}]", f"bf16[{Mr},{W}]")
    _assert_compact_scatter(text, B * L, W, fm.scatter_cap(cap, W))
    kernels = text.count("tpu_custom_call")
    assert kernels == 1, f"{kernels} Mosaic kernels, expected scatter_rows " \
        "alone (the rows go through XLA's blocks)"
    copy = r"= (?:bf16|f32)\[%d,%d\]\S* copy\(" % (Mr, W)
    everywhere = len(re.findall(copy, text))
    at_the_ends = len(re.findall(copy, text[text.index("\nENTRY "):]))
    assert everywhere == at_the_ends == 4, \
        f"{everywhere} copies of a whole table, {at_the_ends} in ENTRY"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 15.1e9, f"{temp / 1e9:.2f} GB of temporaries"


def ffm_joint_megastep_tp4():
    """The same megastep as a chip of four runs it (PR 36): the cell
    ffm_criteo_joint_tp4.stream_mesh's geometry (-dims 2^30: 16,777,216
    rows over `-mesh dp=1,tp=4`, so [4194304, 164] a chip, the one-chip
    flagship's block), `make_ffm_step_fused(mesh=...)` under `shard_map`
    over tp. A chip's program is the one chip's: no zero fill of a
    float32 [4194304, 164] gradient and no table-sized AdaGrad pass
    outside the dense branch of the tail's `conditional`, the compact
    table of the chip's own distinct rows in fast memory, a chip's
    outputs its block of the state and its temporaries the one-chip
    program's.

    The slab crosses the mesh as the blocks' compact tables
    (`ops/fm.py` `ffm_slab_over_blocks`): one `conditional` more, on the
    blocks' largest count of distinct rows, and one `while` more (each
    chip's fill of its own [49152, 164] table, beside the fill of
    `gather_rows`' in the other branch). A batch within the capacities
    moves ONE all-gather, of the tables laid end to end (bf16[196608,164]:
    tp * 49,152 rows, placed in fast memory, which the expansion reads),
    and ONE int32 all-reduce of a row a slot (s32[1277952]), besides
    scalars; the slabs' all-reduce (bf16[32768,39,164], 419 MB), the
    only collective of size before the exchange, is left in the other branch
    alone. Every collective made and NAMED by the partitioner."""
    from hivemall_tpu.ops.scan import make_megastep
    Fj, B, L, ks, tp = 39, 32768, 39, 2, 4
    Mr, W = 1 << 24, Fj * K + 8
    mesh = _mesh_1x4()
    rows, everywhere = NamedSharding(mesh, P("tp", None)), \
        NamedSharding(mesh, P())
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    step = fm.make_ffm_step_fused(get_loss("logloss"), opt, LAMS, Fj, K,
                                  fieldmajor=True, unit_val=True, mesh=mesh)
    cap = fm.tail_cap(B * L, Mr // tp, W, 2)
    assert cap == 174080, cap           # the one-chip flagship's
    ecap = fm.gather_cap(cap, W, 2, tp)
    assert ecap == 49152, ecap
    w0 = _sds((), jnp.float32, everywhere)
    compiled = make_megastep(step.core).lower(
        {"T": _sds((Mr, W), jnp.bfloat16, rows), "w0": w0},
        {"T": {"gg": _sds((Mr, W), jnp.float32, rows)}, "w0": {"gg": w0}},
        w0, _sds((ks,), jnp.int32, everywhere),
        _sds((ks, B, L), jnp.int32, everywhere), None,
        _sds((ks, B), jnp.float32, everywhere), None, None).compile()
    text = compiled.as_text()
    conds, loops = text.count(" conditional("), text.count(" while(")
    assert conds == 4, f"{conds} conditional op(s), expected the list, " \
        "the exchange, the gather of its fallback and the tail"
    assert loops == 5, f"{loops} while op(s), expected the scan, the " \
        "tail's passes, the blocks and two fills"
    block, slab = f"[{Mr // tp},{W}]", f"bf16[{B},{L},{W}]"
    every = f"bf16[{tp * ecap},{W}]"
    # the exchange's true branch expands the slab out of the tables laid
    # end to end; its other branch gathers each block for its slab
    gathers = _slab_gathers(text, slab)
    assert gathers == {
        "branch_1": every,
        "branch_0/branch_1": f"bf16[{fm.gather_cap(cap, W, 2)},{W}]",
        "branch_0/branch_0": f"bf16{block}"}, gathers
    for table in (f"bf16[{ecap},{W}]", f"bf16[{fm.gather_cap(cap, W, 2)},"
                  f"{W}]"):
        spaces = _compact_table_spaces(text, table)
        assert spaces == {"S(1)"}, f"{table} lives in {spaces}"
    # a chip walks all the slab's slots, and drops those of other blocks
    _assert_compact_scatter(text, B * L, W, fm.scatter_cap(cap, W))
    assert text.count("tpu_custom_call") == 1
    # what the parent's GSPMD program ran every step, the zero fill of a
    # table-sized G and the elementwise pass over table and state, is in
    # the dense branch of the tail's cond and nowhere a fitting batch goes
    # (the relayouts at the megastep's two ends are copies, the blocks'
    # row scatters update in place)
    fitting = _fitting_path(text)
    dense = [line.split(" = ")[0].strip() for line in fitting
             if re.search(r"= \(?[^=]*%s[^=]* (broadcast\(|fusion\(.*"
                          r"kind=kLoop)" % re.escape(block), line)]
    assert not dense, f"table-sized passes a fitting batch runs: {dense}"
    # named by the partitioner as its own are (`mesh.collective_share`
    # reads a trace's ops by name; a `lax.psum` would be `psum.N`)
    # (a result may be a tuple: the partitioner combines the stats' small
    # reductions, two counters in one since PR 38)
    kinds = ("all-reduce", "all-gather")

    def named(lines):
        return [(n, k, a) for line in lines for n, r, k in re.findall(
            r"%(\S+) = ([^=]*?) (all-reduce|all-gather)(?:-start)?\(", line)
            for a in re.findall(r"\w+\[[\d,]*\]", r)]
    every_one = named(text.splitlines())
    assert all(n.startswith(k) for n, k, _ in every_one) and len(
        {n for n, _, _ in every_one}) == sum(
            len(re.findall(r" %s(?:-start)?\(" % k, text)) for k in kinds), \
        f"a collective unnamed: {[n for n, _, _ in every_one]}"

    def big(found):
        return sorted((k, a) for _, k, a in found if np.prod(
            [int(d) for d in re.findall(r"\d+", a)[1:]]) > 64)
    assert big(every_one) == sorted([
        ("all-gather", every), ("all-reduce", f"s32[{B * L}]"),
        ("all-reduce", slab)]), big(every_one)
    assert big(named(fitting)) == sorted([
        ("all-gather", every), ("all-reduce", f"s32[{B * L}]")]), \
        big(named(fitting))
    for kind in ("collective-permute", "all-to-all", "reduce-scatter"):
        assert f" {kind}(" not in text and f" {kind}-start(" not in text, \
            f"{kind} in the sharded megastep"
    # a chip's outputs are its block of the state, its temporaries no
    # more than the one chip's program reads (15.07 GB since PR 38, the
    # dense branch's buffers, which the chip overlays: see there)
    mem = compiled.memory_analysis()
    state = Mr // tp * W * (2 + 4)
    assert abs(mem.output_size_in_bytes - state) < 0.05 * state, mem
    assert mem.temp_size_in_bytes <= 15.1e9, mem


def state_init():
    """The fused tables' initialiser (models/fm.py `_fused_state_init`,
    the function `LearnerBase._make_state` jits) at the size no one chip
    holds: `train_ffm -dims 2^30 -halffloat` over tp=4, each output in its
    row sharding. A chip holds its quarter of the table and of the AdaGrad
    state (4.13 GB; 4.23 as the compiler lays it out) and draws it
    locally, with no collective in the program and the whole of it inside
    the chip."""
    from hivemall_tpu.models.fm import _fused_state_init
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         reg="no")
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    mesh = _mesh_1x4()
    rows, everywhere = NamedSharding(mesh, P("tp", None)), \
        NamedSharding(mesh, P())
    Mr, FK = 1 << 24, 39 * 4
    init = _fused_state_init(opt, Mr, 1, FK, FK + 8, jnp.bfloat16)
    out = ({"w0": everywhere, "T": rows},
           {"w0": {"gg": everywhere}, "T": {"gg": rows}})
    compiled = jax.jit(init, out_shardings=out).lower(
        _sds(key.shape, key.dtype, everywhere),
        _sds((), jnp.float32, everywhere)).compile()
    text = compiled.as_text()
    for kind in ("all-reduce", "all-gather", "collective-permute",
                 "all-to-all", "reduce-scatter"):
        assert f" {kind}(" not in text, f"{kind} in the sharded initialiser"
    mem = compiled.memory_analysis()
    quarter = Mr // 4 * (FK + 8) * (2 + 4)
    assert abs(mem.output_size_in_bytes - quarter) < 0.05 * quarter, \
        f"{mem.output_size_in_bytes / 1e9:.2f} GB of outputs a chip"
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


def linear_megastep():
    """train_classifier -loss logloss -opt adagrad (AdaGrad-RDA) at the
    geometry of the benchmark's cell logreg_criteo.stream (-dims 2^28:
    `w`, `u`, `gg` three float32 [2^28] arrays; B=32768, L=39, unit values
    elided): the state's jitted initialiser (models/linear.py
    `_linear_state_init`, what `LearnerBase._make_state` jits) and the
    megastep (two steps: a scan's body compiles once) of the distinct-slot
    step, its `finish` after the scan. The state is 3.22 GB and
    stays inside one chip with the step's temporaries, each of the four
    phase scopes names ops of the step, the three arrays are updated in
    place, no whole array is copied, and a batch within the capacity
    (the `cond`'s distinct branch) runs no pass over a [2^28] array: the
    one such pass is `finish`'s rebuild of `w`, once a dispatch, after the
    scan; its state is written by sorted scatters in place, its runs
    summed by the one Pallas kernel, `fold_runs`."""
    from hivemall_tpu.models.linear import _linear_state_init
    from hivemall_tpu.ops.linear import distinct_cap, make_linear_step
    from hivemall_tpu.ops.scan import make_megastep
    dims, B, L, ks = 1 << 28, 32768, 39, 2
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                         power_t=0.1, reg="rda", lam=1e-6)
    assert opt.name == "adagrad_rda" and opt.weight_of is not None
    init = jax.jit(_linear_state_init(opt, dims, jnp.float32),
                   out_shardings=(DEV0, {"u": DEV0, "gg": DEV0})).lower() \
        .compile().memory_analysis()
    assert 0 <= init.output_size_in_bytes - 3 * 4 * dims < 4096 \
        and init.temp_size_in_bytes == 0, init
    step = make_linear_step(get_loss("logloss"), opt)
    table = _sds((dims,), jnp.float32)
    compiled = make_megastep(step.core, none_val=True, finish=step.finish
                             ).lower(
        table, {"u": table, "gg": table}, _sds((), jnp.float32),
        _sds((ks,), jnp.int32), _sds((ks, B, L), jnp.int32), None,
        _sds((ks, B), jnp.float32), None, None).compile()
    text = compiled.as_text()
    for scope in ("hm.gather", "hm.grad", "hm.scatter", "hm.update"):
        assert f"/{scope}/" in text, f"no op under {scope}"
    cap = distinct_cap(B * L, dims)
    assert 74_000 < cap < B * L and f"f32[{cap}]" in text, cap
    assert text.count(" conditional(") == 1, "one cond: distinct or dense"
    kernels = text.count("tpu_custom_call")
    assert kernels == 2 and "fold_runs" in text, \
        f"{kernels} Mosaic kernels, expected fold_runs' pass before and " \
        "in its loop"
    big = r"f32\[%d\]" % dims
    assert not re.findall(r"= %s\S* copy\(" % big, text), "a whole copy"
    passes = [line for line in _fitting_path(text)
              if re.search(r"= %s\S* fusion\(.*kind=kLoop" % big, line)]
    assert len(passes) == 1 and "/hm.update/" in passes[0] \
        and "/hm.scan/" not in passes[0], \
        f"table-sized passes a fitting batch runs: {passes}"
    mem = compiled.memory_analysis()
    # donated: the three arrays are updated in place. The temporaries
    # are the dense branch's (a batch past the capacity): one table-sized
    # array, 1.11 GB read here (the cell's traced runs peak at 3.60-3.68
    # GB of HBM, under the dense step's 3.83)
    assert mem.alias_size_in_bytes >= 3 * 4 * dims
    assert mem.temp_size_in_bytes < 1.2e9, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30


def skipgram_megastep():
    """train_word2vec -dim 300 at the geometry of the benchmark's cell
    skipgram_enwiki.stream_text (2,519,370 words, 8 steps of 16,384 pair
    slots, the 2^27-slot noise table as the megastep's broadcast extra):
    the state's jitted initialiser and the megastep. Rows are stored 384
    lanes wide, so both tables keep the row-major layout at the program's
    two ends (a [V, 300] array is kept TRANSPOSED, `{0,1}`, and relayouted
    four times a dispatch with 7.8 GB of temporaries: PERF.md section 6,
    PR 37); state, noise table and temporaries stay inside one chip;
    every phase scope names ops, the draw under hm.sample."""
    from hivemall_tpu.models import word2vec as w2v
    from hivemall_tpu.ops.scan import make_megastep
    V, B, ks = 2_519_370, 16384, 2
    lanes = w2v.row_lanes(300)
    assert lanes == 384
    tables = {"in": DEV0, "out": DEV0}
    init = jax.jit(w2v._state_init(V, 300),
                   out_shardings=(tables, {"words": DEV0})).lower(
        _sds((2,), jnp.uint32)).compile().memory_analysis()
    assert 0 <= init.output_size_in_bytes - 2 * 4 * V * lanes < 65536 \
        and init.temp_size_in_bytes == 0, init
    step = w2v._step_cached(5, 27, 0.025, 1.7e9 * 5, False)
    table = _sds((V, lanes), jnp.float32)
    compiled = make_megastep(step.core, none_val=True).lower(
        {"in": table, "out": table}, {"words": _sds((2,), jnp.int32)},
        _sds((), jnp.float32), _sds((ks,), jnp.int32),
        _sds((ks, B, 5), jnp.int32), None, _sds((ks, B), jnp.float32), None,
        _sds((1 << 27,), jnp.int32)).compile()
    text = compiled.as_text()
    for scope in ("hm.sample/hm.gather", "hm.gather", "hm.grad",
                  "hm.scatter", "hm.update"):
        assert f"/{scope}/" in text, f"no op under {scope}"
    assert "tpu_custom_call" not in text
    shape = f"f32[{V},{lanes}]"
    assert shape + "{1,0:" in text and shape + "{0,1:" not in text
    assert not re.search(r" copy\(%[\w.]*\)[^\n]*" + re.escape(shape), text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * V * lanes
    assert mem.temp_size_in_bytes < 2 ** 30, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30


def mf_megastep():
    """train_mf_adagrad at the geometry of the benchmark's cell
    mf_hugewiki.stream_ratings (-factors 100 -users 6260326 -items 39780,
    rows stored 128 float32 lanes; 8 steps of 131,072 ratings, here two):
    the state's jitted initialiser (6.45 GB, born on the chip) and the
    megastep. Both tables go through the distinct-row tail: the user
    table's capacity from `tail_cap`, the item table's from
    `whole_table_cap`; each has its compact table in fast memory and its
    two Mosaic kernels (`scatter_rows`, `update_rows`: four in all); no
    whole table is copied or relayouted, every phase scope names ops, and
    state and temporaries stay inside one chip (the temporaries are the
    user table's dense tail, 3.28 GB read here)."""
    from hivemall_tpu.models import mf
    from hivemall_tpu.ops.optimizers import make_optimizer_cached
    from hivemall_tpu.ops.scan import make_megastep
    U, I, k, B, ks = 6_260_326, 39_780, 100, 131072, 2
    W = mf.row_lanes(k)
    assert W == 128
    opt = make_optimizer_cached("adagrad", "fixed", 0.01, 10_000, 0.1)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    tables = {"U": DEV0, "I": DEV0}
    init = jax.jit(mf._state_init(U, I, k, 0.1, "float32", opt),
                   out_shardings=(tables, {"U": {"gg": DEV0},
                                           "I": {"gg": DEV0}})).lower(
        _sds(key.shape, key.dtype)).compile().memory_analysis()
    state = 2 * 4 * W * (U + I)
    assert 0 <= init.output_size_in_bytes - state < 65536 \
        and init.temp_size_in_bytes == 0, init
    caps = {"U": fm.tail_cap(B, U), "I": fm.whole_table_cap(B, I)}
    assert caps == {"U": B, "I": 38912} and fm.tail_cap(B, I) == 0, caps
    step = mf._step_cached(k, 3.0, 0.01, True, opt, True)
    Ut, It = _sds((U, W), jnp.float32), _sds((I, W), jnp.float32)
    compiled = make_megastep(step.core, none_val=True).lower(
        {"U": Ut, "I": It}, {"U": {"gg": Ut}, "I": {"gg": It}},
        _sds((), jnp.float32), _sds((ks,), jnp.int32),
        _sds((ks, B, 2), jnp.int32), None, _sds((ks, B), jnp.float32), None,
        None).compile()
    text = compiled.as_text()
    for scope in ("hm.gather", "hm.grad", "hm.scatter", "hm.update"):
        assert f"/{scope}/" in text, f"no op under {scope}"
    kernels = text.count("tpu_custom_call")
    assert kernels == 4, \
        f"{kernels} Mosaic kernels, expected scatter_rows and update_rows " \
        "a table"
    for t, cap in caps.items():
        spaces = _compact_table_spaces(text, f"f32[{cap},{W}]")
        assert spaces == {"S(1)"}, f"{t}'s compact table lives in {spaces}"
    for R in (U, I):
        shape = f"f32[{R},{W}]"
        assert shape + "{1,0" in text and shape + "{0,1" not in text
        assert not re.findall(r"= %s\S* copy\(" % re.escape(shape), text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 3.4e9, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30


N, D, BINS = 1 << 20, 28, 64            # HIGGS-shaped trees, cut to 1M rows


def _hist(fn, n_nodes, fast):
    jax.jit(lambda b, l, w: fn(b, l, w, n_nodes, BINS, fast=fast)).lower(
        _sds((N, D), jnp.uint8), _sds((N,), jnp.int32),
        _sds((N, 3), jnp.float32)).compile()


def hist_flat():
    for m in (1, 8):
        _hist(pallas_hist.level_histogram, m, False)


def hist_dense():
    dp = -(-D // 8) * 8
    for m, fast in ((1, True), (64, True), (8, False)):
        jax.jit(lambda b, l, w: pallas_hist.level_histogram_dense(
            b, l, w, m, BINS, fast=fast)).lower(
            _sds((dp, N), jnp.uint8), _sds((N,), jnp.int32),
            _sds((N, 3), jnp.float32)).compile()


def hist_sorted():
    for m in (64, 256):
        _hist(pallas_hist.level_histogram_sorted, m, False)


CASES = {f.__name__: f for f in (fm_minibatch_step,
                                 ffm_joint_megastep, ffm_joint_megastep_tp4,
                                 hist_flat, hist_dense,
                                 hist_sorted, state_init, linear_megastep,
                                 skipgram_megastep, mf_megastep)}

if __name__ == "__main__":
    for name in sys.argv[1:]:
        t0 = time.perf_counter()
        CASES[name]()
        print(json.dumps({"case": name, "ok": True, "seconds":
                          round(time.perf_counter() - t0, 1)}), flush=True)
