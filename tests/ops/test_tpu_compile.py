"""Main-path device programs compile for a TPU v5e chip at real widths.

Nothing runs: each program is lowered and compiled for one chip of a
described ``v5e:2x2`` topology, which refuses what the chip's compiler
would (VMEM overflow, unaligned tiles, programs that do not fit HBM).
The topology is described inside a fixture — never at import — and the
persistent compile cache is off around these compiles (a described-device
entry cannot be read back without a chip).
"""

import os

import numpy as np
import pytest

#: one v5e chip's HBM
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                sharding=sharding)


def _like(sharding, tree):
    """ShapeDtypeStructs on the described chip for a tree of host arrays."""
    import jax

    return jax.tree.map(
        lambda a: _spec(sharding, np.shape(a), np.asarray(a).dtype), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_pallas_keccak_4096_lanes(one_chip):
    from mythril_tpu.ops import keccak_pallas

    compiled = keccak_pallas._permute_tile.lower(
        _spec(one_chip, (100, 4096), np.uint32), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tape_vm_at_the_4096_candidate_bucket(one_chip):
    from mythril_tpu.ops import tape_vm

    _name, T, V, A, K, R = tape_vm._PROFILES[-1]
    B, L = 4096, tape_vm.L
    shapes = [
        ((B, V, L), np.uint32), ((B, A, K, L), np.uint32),
        ((B, A, K, L), np.uint32), ((B, A, K), bool), ((B, A, L), np.uint32),
        *[((T,), np.int32)] * 5,
        ((T, L), np.uint32), ((R,), np.int32), ((R,), bool),
    ]
    compiled = tape_vm._run_tape.lower(
        *[_spec(one_chip, s, d) for s, d in shapes],
        T=T, V=V, A=A, K=K, R=R).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_frontier_segment_width_1024(one_chip):
    """The segment of ``Caps(B=1024)`` at the size bucket the width-1024
    wide contract and the BECToken-shaped contract both land in."""
    from mythril_tpu.frontier.arena import HostArena
    from mythril_tpu.frontier.code import CodeTables, stacked_device_tables
    from mythril_tpu.frontier.state import Caps, empty_state
    from mythril_tpu.frontier.step import (
        ArenaDev,
        CfgScalars,
        CodeDev,
        build_segment,
    )
    from collections import namedtuple

    caps = Caps(B=1024)
    bucket = (1, 512, 32768, 512)  # (code, instr, addr, loops) caps
    Ins = namedtuple("Ins", "opcode address arg_int")
    arena = HostArena(caps.ARENA)
    tables = CodeTables([Ins("STOP", 0, None)], arena)
    code = CodeDev(*stacked_device_tables([tables], bucket))
    cfg = CfgScalars(*[np.int32(0)] * len(CfgScalars._fields))
    args = (
        empty_state(caps, bucket[3]),
        ArenaDev(*arena.device_arrays()),
        np.int32(0),
        np.zeros((3, bucket[0], bucket[1]), bool),
        code,
        cfg,
    )
    compiled = build_segment(caps).lower(*_like(one_chip, args)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("nodes,rows", [(512, 16), (4096, 64)])
def test_absdomain_known_bits(one_chip, nodes, rows):
    from mythril_tpu.absdomain import device, tape
    from mythril_tpu.smt import terms

    x = terms.var("tpu_compile_kb", 256)
    *small, n_nodes = device.chunk_args(
        tape.pack([[terms.ult(x, terms.const(3, 256))]]), [0])
    nb, rb = small[0].shape[0], small[-1].shape[1]
    # re-bucket every node (leading) and row (second) axis; the trip count
    # stays a 0-d int32
    specs = [
        _spec(one_chip,
              (nodes if a.shape[0] == nb else a.shape[0],)
              + ((rows,) if a.ndim == 3 and a.shape[1] == rb else a.shape[1:2])
              + a.shape[2:],
              a.dtype)
        for a in small
    ] + [_spec(one_chip, (), n_nodes.dtype)]
    compiled = device._get_jitted().lower(*specs).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("q,c,v", [(4, 512, 512), (16, 4096, 4096)])
def test_devsolver_kernel(one_chip, q, c, v):
    from mythril_tpu.devsolver import device, kernel

    plane = kernel.pack_plane([([[4]], [2])], n_vars=3)
    lits = _spec(one_chip, (q, c) + plane.lits.shape[2:], plane.lits.dtype)
    dec = _spec(one_chip, (q,) + plane.dec.shape[1:], plane.dec.dtype)
    compiled = device._get_jitted().lower(
        lits, dec, _spec(one_chip, (v,), np.int8),
        _spec(one_chip, (), np.int32)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
