"""Device ops picked by the op-name scope of their HLO metadata: the
programs' HLO on a trace's metadata plane, each op's program from its
``program_id`` stat or the ``XLA Modules`` span around it; on a
hand-made XSpace and on the metadata plane of a trace the profiler wrote."""

import glob

import jax
import jax.numpy as jnp

import hloscope as H

SCOPE = "deep_topk_select"
IN = "jit(daat_serve)/jit(deep_topk_select)/"


def _hlo(name, pid, ops):
    hlo = H.message("HloProto")
    hlo.hlo_module.name, hlo.hlo_module.id = name, pid
    comp = hlo.hlo_module.computations.add()
    for op, op_name in ops:
        ins = comp.instructions.add(name=op)
        ins.metadata.op_name = op_name
    return hlo.SerializeToString()


def _space():
    sp = H.message("XSpace")
    meta = sp.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=1).value.name = "Hlo Proto"
    for pid, name, ops in [
            (7, "jit_daat_serve", [("sort.2", IN + "top_k"),
                                   ("fusion.3", IN + "gather"),
                                   ("fusion.4", "jit(daat_serve)/add")]),
            (9, "jit_rerank", [("sort.2", "jit(rerank)/top_k")])]:
        e = meta.event_metadata.add(key=pid)
        # a module name with its program id, and one without
        e.value.name = f"{name}({pid})" if pid == 9 else name
        e.value.stats.add(metadata_id=1, bytes_value=_hlo(name, pid, ops))
    dev = sp.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=1).value.name = "program_id"
    dev.stat_metadata.add(key=2).value.name = "tf_op"
    names = ["jit_daat_serve(7)", "jit_rerank(9)", "sort.2", "fusion.3",
             "fusion.4", "%reduce_max.0 = s32[8,1024] reduce(..)"]
    for i, n in enumerate(names):
        dev.event_metadata.add(key=i + 1).value.name = n
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    for md, at, dur in [(1, 0, 100_000), (2, 200_000, 100_000)]:
        mods.events.add(metadata_id=md, offset_ps=at, duration_ps=dur)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for md, at in [(3, 10_000), (4, 20_000), (5, 30_000),   # in jit_daat
                   (3, 210_000),                            # in jit_rerank
                   (6, 400_000), (3, 500_000), (3, 900_000)]:
        ops.events.add(metadata_id=md, offset_ps=at, duration_ps=5_000)
    ev = ops.events[4]                                      # tf_op stat
    ev.stats.add(metadata_id=2, str_value=IN + "reduce_max")
    ev = ops.events[5]                                      # program_id stat
    ev.stats.add(metadata_id=1, int64_value=7)
    return sp


def test_scoped_ops_by_program_and_by_stat():
    sp = H.message("XSpace")
    sp.ParseFromString(_space().SerializeToString())
    got = H.scoped_ops(sp, SCOPE, 0, 1800)
    # sort.2 and fusion.3 of jit_daat_serve, not jit_rerank's sort.2;
    # the op with the scope in its tf_op stat; the sort.2 that names its
    # program; not the one outside the window (start 1900)
    assert got == [["sort.2", 1010.0, 5.0], ["fusion.3", 1020.0, 5.0],
                   ["reduce_max.0", 1400.0, 5.0], ["sort.2", 1500.0, 5.0]]
    assert H.scoped_ops(sp, "no_such_scope", 0, 1e9) == []


def test_scoped_instructions_of_a_cpu_trace(tmp_path):
    """The profiler keeps each program's HLO on the metadata plane: a
    nested jit's name reaches its instructions' op names."""
    @jax.jit
    def outer(x):
        return inner(x + 1) * 2

    @jax.jit
    def inner(x):
        return jax.lax.top_k(x, 3)[0]

    x = jnp.arange(64.0).reshape(4, 16)
    outer(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    outer(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    named = H.scoped_instructions(H.load(path), "jit(inner)")
    assert any(names for names in named.values())
    assert not any(H.scoped_instructions(H.load(path), "jit(nowhere)")
                   .values())
