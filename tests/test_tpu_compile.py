"""Compile rehearsals for the TPU: the serve path's Pallas kernels and the
full-width decode tick, compiled for a described v5e chip.

Nothing here runs on a chip.  The TPU compiler, which is installed beside
JAX, compiles for a described ``v5e:2x2`` topology, so these tests catch
what interpret mode cannot — block shapes off the (8, 128) tiling, too much
fast memory, a program that does not fit 16 GB — at the widths the chip
serves: stablelm-1.6b (32 heads × 64, d 2048, 24 layers, bf16) and the
paper's VGA frames (480 × 640 × 3) on the codec path, and deepseek-v2's
share of one expert-parallel chip (latent attention, 20 of 160 experts).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU here, so
the tests steer the kernels out of interpret mode themselves.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import compression as comp
from repro.core.buffers import StreamBuffer
from repro.kernels import ops as kops
from repro.kernels.flash_attn import flash_attention, flash_blocks
from repro.kernels.quant8 import dequantize8_pallas, quantize8_pallas
from repro.kernels.ref import SPARSE_B, _sparse_dims
from repro.kernels.sparse_dec import sparse_dec_pallas
from repro.kernels.sparse_enc import sparse_enc_pallas

VGA_FRAME = (480, 640, 3)
V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Trace the model code's Pallas calls for the chip, not interpreted."""
    monkeypatch.setattr(kops, "use_interpret", lambda: False)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prompt", [7, 64, 600])
def test_flash_prefill_kernel_at_stablelm_widths(one_chip, prompt):
    sp, blk = flash_blocks(prompt)
    qkv = jax.ShapeDtypeStruct((32, sp, 64), jnp.bfloat16, sharding=one_chip)
    c = _compile(functools.partial(flash_attention, causal=True, bq=blk,
                                   bk=blk, interpret=False), qkv, qkv, qkv)
    assert "tpu_custom_call" in c.as_text()


def _vga_quant_view():
    """The padded 2-D view the quant8 codec gives a float VGA frame."""
    frame = jax.ShapeDtypeStruct(VGA_FRAME, jnp.float32)
    return jax.eval_shape(lambda x: kops._pad_tiles(kops._as2d(x)), frame)


def _vga_sparse_dims():
    n = VGA_FRAME[0] * VGA_FRAME[1] * VGA_FRAME[2]
    return _sparse_dims(n, comp._sparse_cap(n, 0.25))


def test_quantize8_kernel_at_vga(one_chip):
    view = _vga_quant_view()
    c = _compile(functools.partial(quantize8_pallas, interpret=False),
                 _on(one_chip, view))
    assert "tpu_custom_call" in c.as_text()


def test_dequantize8_kernel_at_vga(one_chip):
    view = _vga_quant_view()
    q = jax.ShapeDtypeStruct(view.shape, jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((view.shape[0] // 32, view.shape[1] // 128),
                             jnp.float32, sharding=one_chip)
    c = _compile(functools.partial(dequantize8_pallas, interpret=False), q, s)
    assert "tpu_custom_call" in c.as_text()


def test_sparse_enc_kernel_at_vga(one_chip):
    nb, kb = _vga_sparse_dims()
    flat = jax.ShapeDtypeStruct((nb * SPARSE_B,), jnp.float32,
                                sharding=one_chip)
    c = _compile(functools.partial(sparse_enc_pallas, kb=kb, interpret=False),
                 flat)
    assert "tpu_custom_call" in c.as_text()


def test_sparse_dec_kernel_at_vga(one_chip):
    nb, kb = _vga_sparse_dims()
    v = jax.ShapeDtypeStruct((nb, kb), jnp.float32, sharding=one_chip)
    i = jax.ShapeDtypeStruct((nb, kb), jnp.int32, sharding=one_chip)
    c = _compile(functools.partial(sparse_dec_pallas, interpret=False), v, i)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("codec", ["quant8", "sparse"])
def test_batched_codec_programs_at_vga(one_chip, compiled_kernels, codec):
    """``encode_batch``/``decode_batch`` over two VGA frames: each one
    program around the Pallas kernels (the frames' reshapes to and from the
    flat wire view, compiled op by op, took the TPU compiler minutes)."""
    frames = StreamBuffer(
        tensors=(jax.ShapeDtypeStruct((2,) + VGA_FRAME, jnp.float32),),
        pts=jax.ShapeDtypeStruct((), jnp.int32), meta={})
    enc = comp._encode_stacked_jit.lower(_on(one_chip, frames),
                                         codec=codec).compile()
    wire, _ = jax.eval_shape(lambda f: comp.encode_stacked(f, codec), frames)
    dec = comp._decode_stacked_jit.lower(_on(one_chip, wire),
                                         codec=codec).compile()
    assert "tpu_custom_call" in enc.as_text()
    assert "tpu_custom_call" in dec.as_text()


# ---------------------------------------------------------------------------
# the served programs at full width
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stablelm_server():
    from repro.launch import model_serve as ms
    ps = ms.serve_pipeline(model="stablelm-1.6b", slots=8, max_seq=1024)
    ps.realize()
    return ps


def test_full_width_prefill_holds_the_flash_kernel(one_chip, stablelm_server,
                                                   compiled_kernels):
    """The program ``host_prefill`` compiles for a 600-token prompt."""
    from repro.models import transformer
    elem = stablelm_server.elements["lm"]
    cfg, max_seq = elem.cfg, elem.max_seq
    params = _on(one_chip, jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.PRNGKey(0)))

    def prefill(p, toks):
        logits, cache = transformer.lm_prefill(p, cfg, toks[None], max_seq)
        return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), cache
    toks = jax.ShapeDtypeStruct((600,), jnp.int32, sharding=one_chip)
    c = _compile(prefill, params, toks)
    assert "tpu_custom_call" in c.as_text()


def _join_all(elem):
    """The admit lanes of a tick on which every slot joins."""
    return elem.build_admit([(i, 0, 1, None) for i in range(elem.slots)])


def test_full_width_join_tick_fits_one_chip(one_chip, stablelm_server):
    """The 24-layer, 8-slot decode tick with every slot joining (the
    joiners' 1024-position caches are in their rows already; the bundle
    carries their lanes)."""
    ps = stablelm_server
    elem = ps.elements["lm"]
    params = _on(one_chip, jax.eval_shape(ps.init, jax.random.PRNGKey(0)))
    state = _on(one_chip, jax.eval_shape(ps.init_state))
    admit = _on(one_chip, _join_all(elem))
    src = ps.plan.query_sources[0].name
    tick = ps.plan.compiled_serve_tick(state, donate=True)
    c = tick.lower(params, state, {src: admit}).compile()
    mem = c.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < V5E_HBM, f"join tick needs {live / 1e9:.2f} GB"


@pytest.fixture(scope="module")
def deepseek_server():
    from repro.launch import model_serve as ms
    ps = ms.serve_pipeline(model="deepseek-v2-236b-ep8", slots=64,
                           max_seq=2048)
    ps.realize()
    return ps


@pytest.mark.parametrize("join", [False, True], ids=["nojoin", "join"])
def test_deepseek_tick_fits_one_chip(one_chip, deepseek_server, join):
    """deepseek-v2's share of one EP8 chip (5 layers, 20 of 160 experts
    held, bf16) serving 64 slots x 2048 latent positions: the no-join tick
    and the tick where every slot joins fit 16 GB."""
    ps = deepseek_server
    elem = ps.elements["lm"]
    params = _on(one_chip, jax.eval_shape(ps.init, jax.random.PRNGKey(0)))
    state = _on(one_chip, jax.eval_shape(ps.init_state))
    s = elem.slots
    if join:
        admit = _join_all(elem)
    else:
        admit = StreamBuffer(tensors=(jax.ShapeDtypeStruct((s,), jnp.bool_),),
                             meta={"empty": True})
    src = ps.plan.query_sources[0].name
    tick = ps.plan.compiled_serve_tick(state, donate=True)
    c = tick.lower(params, state, {src: _on(one_chip, admit)}).compile()
    mem = c.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < V5E_HBM, f"tick needs {live / 1e9:.2f} GB"


@pytest.mark.parametrize("server", ["stablelm_server", "deepseek_server"])
def test_full_width_row_write_happens_in_place(one_chip, request, server):
    """The join's row write at full width, donated as on the chip: the
    slot table's output aliases its input, so a joiner moves one row and
    the table is never held twice."""
    ps = request.getfixturevalue(server)
    elem = ps.elements["lm"]
    table = _on(one_chip, jax.eval_shape(ps.init_state)["lm"]["cache"])
    row = _on(one_chip, jax.eval_shape(elem._cache_template))
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    c = elem.row_writer(donate=True).lower(table, slot, row).compile()
    mem = c.memory_analysis()
    size = sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(table))
    assert mem.alias_size_in_bytes >= size
    assert mem.temp_size_in_bytes < size / elem.slots


def _table_sized_writes(hlo_text, sizes):
    """Top-level fusions, selects and copies of the compiled program whose
    output holds an array of one of ``sizes`` elements."""
    entry = re.search(r"^ENTRY .*?^}", hlo_text, re.M | re.S).group(0)
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) (fusion|select|copy)\(",
                     line)
        if m and sizes & {math.prod(int(d) for d in dims.split(",") if d)
                          for dims in re.findall(r"\w+\[([\d,]*)\]",
                                                 m.group(2))}:
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("model,slots,max_seq", [
    ("stablelm-1.6b", 16, 1024), ("deepseek-v2-236b-ep8", 64, 2048)],
    ids=["stablelm", "deepseek"])
def test_no_join_tick_commits_rows_not_the_table(one_chip, model, slots,
                                                 max_seq):
    """The benchmark cells' no-join ticks, donated as on the chip: each
    layer commits the one row it writes, so outside the row writes nothing
    writes an array the size of a slot-table leaf — no select of the
    table, and no copy of it into another layout — and the tick holds no
    table-sized temporary (a tree-wide ``where(active, new, old)`` commit
    compiled to 3.21 GB and 1.19 GB)."""
    from repro.launch import model_serve as ms
    ps = ms.serve_pipeline(model=model, slots=slots, max_seq=max_seq)
    ps.realize()
    params = _on(one_chip, jax.eval_shape(ps.init, jax.random.PRNGKey(0)))
    state = _on(one_chip, jax.eval_shape(ps.init_state))
    admit = StreamBuffer(tensors=(jax.ShapeDtypeStruct((slots,), jnp.bool_),),
                         meta={"empty": True})
    src = ps.plan.query_sources[0].name
    tick = ps.plan.compiled_serve_tick(state, donate=True)
    c = tick.lower(params, state, {src: _on(one_chip, admit)}).compile()
    leaves = {l.size for l in jax.tree_util.tree_leaves(state["lm"]["cache"])
              if l.ndim > 2}
    assert _table_sized_writes(c.as_text(), leaves) == []
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < 0.5e9, f"tick holds {temp / 1e9:.2f} GB of temporaries"


def test_deepseek_expert_layer_work_follows_routed_tokens(one_chip):
    """One expert layer's routed part over the 64-slot batch as the tick
    maps it (``jax.vmap`` over slots of one token): the compiled program's
    operations stay within 3x of the work its routed tokens need (64 x 6 x
    20/160 = 48 assignments to held experts), where running every held
    expert for every slot would be 27x.  XLA counts the dispatch loop's
    body once: one block of 8 rows, the loop runs once per block present."""
    from repro.launch import model_serve as ms
    from repro.models import moe as MOE
    cfg = ms.SERVE_MODELS["deepseek-v2-236b-ep8"]()
    p = jax.eval_shape(lambda k: MOE.moe_init(k, cfg), jax.random.PRNGKey(0))
    del p["shared"]
    p = _on(one_chip, p)
    x = jax.ShapeDtypeStruct((64, 1, 1, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    c = _compile(jax.vmap(lambda p, x: MOE.moe_serve(p, cfg, x),
                          in_axes=(None, 0)), p, x)
    cost = c.cost_analysis()
    flops = (cost[0] if isinstance(cost, list) else cost)["flops"]
    expert = 2 * 3 * cfg.d_model * cfg.d_ff_expert
    routed = expert * 64 * cfg.top_k * cfg.experts_held // cfg.n_experts
    assert 8 * expert <= flops <= 3 * routed, (flops, routed)


def test_mesh_sharded_burst_compiles_for_four_chips(topo):
    """§4 mesh-sharded offloading: a burst of eight VGA frames laid out
    over a described four-chip mesh (the ``chip_smoke.py --chips 4`` path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import TensorSpec, parse_launch
    from repro.core.elements import register_model
    from repro.launch.mesh import batch_spec, make_mesh
    register_model("compile_relu_vga", lambda rng: {},
                   lambda p, x: jax.nn.relu(x),
                   out_specs=(TensorSpec(VGA_FRAME, "float32"),))
    srv = parse_launch(
        "tensor_query_serversrc operation=frames name=ssrc ! "
        "tensor_filter model=compile_relu_vga ! "
        "tensor_query_serversink name=ssink")
    srv.elements["ssink"].pair_with(srv.elements["ssrc"])
    srv.realize()
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    frames = StreamBuffer(
        tensors=(jax.ShapeDtypeStruct((8,) + VGA_FRAME, jnp.float32),),
        pts=jax.ShapeDtypeStruct((8,), jnp.int32))
    frames = _on(NamedSharding(mesh, P(batch_spec(mesh))), frames)
    step = srv.plan.compiled_step_n(hoist_io=True, hoist_queries=True,
                                    mesh=mesh)
    c = step.lower({}, {}, {"ssrc": frames}).compile()
    assert c.as_text()
