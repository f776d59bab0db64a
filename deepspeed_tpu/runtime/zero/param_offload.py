"""ZeRO-Infinity parameter offload for TRAINING — models larger than HBM.

Reference machinery matched: ``zero_optimization.offload_param`` —
``runtime/zero/partition_parameters.py:616`` (``remote_device``
"cpu"|"nvme"), ``swap_tensor/partitioned_param_swapper.py`` (NVMe param
tier), and stage3's prefetch/release discipline
(``runtime/zero/stage3.py:485,1662,1711``) — the capability behind the
reference's "13B trainable on one 32 GB V100" headline
(``docs/_pages/training.md:302``).

TPU-native shape: instead of stage3's per-parameter gather/partition hooks,
the scan-stacked transformer block is streamed through the chip one layer
at a time, twice per step:

* **forward**: layer ``i``'s packed bf16 buffer is uploaded (JAX async
  dispatch double-buffers upload against compute), one jitted block-apply
  reused for every layer produces the boundary activation; only the L+1
  boundary activations stay device-resident (layer-granular activation
  checkpointing by construction).
* **backward**: layers stream in REVERSE; one jitted ``vjp`` per layer
  recomputes the block forward and yields (dx, layer grads). Layer grads
  leave the chip immediately (``copy_to_host_async``) and accumulate into
  host fp32 buffers — the device never holds more than a couple of layers
  of parameters or gradients. Under a data-parallel mesh the grads'
  replicated out-sharding makes XLA insert the cross-replica reduction
  per layer (the reference's reduce-scatter-as-you-go, stage3.py:1065).
* **update**: the host-side :class:`OffloadedOptimizer` (native SIMD Adam,
  optionally NVMe-swapped state) applies the step and the new bf16 params
  replace the host/NVMe store. Device HBM holds O(boundary activations +
  2 layer buffers + resident embeddings/head) — independent of depth.

With ``device: nvme`` the packed per-layer buffers live in files moved by
the async AIO tier (``ops/csrc/aio.cpp``) with read-ahead, so host DRAM
holds O(staging buffers), not O(model). (The post-step rewrite currently
materializes the new param tree transiently in DRAM — device memory is
bounded by streaming; host DRAM must hold one bf16 copy of the model.
The reference's swapper shares this param-sized host staging requirement
via its pinned buffer pools.)

Engine surface: ``zero_optimization.offload_param.device: "cpu"|"nvme"``
turns this on inside :class:`~deepspeed_tpu.runtime.engine.DeepSpeedEngine`
(train via ``train_batch``; the eager triple does not compose with
streaming).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...parallel import mesh as mesh_mod
from ...utils.logging import log_dist
from ...utils.streaming import LayerWireFormat
from .offload import OffloadedOptimizer, _flatten_with_paths, _unflatten_like
from .offload_config import OffloadDeviceEnum


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _writable_tree(tree):
    """Leaf-wise: keep writable numpy arrays, copy anything else (numpy
    views of jax arrays are read-only; write_layer mutates rows in place)."""
    return jax.tree_util.tree_map(
        lambda a: a if getattr(a, "flags", None) is not None
        and a.flags.writeable else np.array(a), tree)


def _rank_dir(path: str) -> str:
    """Rank-namespace an NVMe directory under multi-process launch: the
    per-layer param/grad files and optimizer leaf files are rank-agnostic
    names, and two same-host processes sharing one dir would read each
    other's half-written files (no cross-rank barrier inside the
    finalize). Each process keeps its own full replica, same as the cpu
    tier's host arrays."""
    if jax.process_count() > 1:
        import os

        return os.path.join(path, f"rank{jax.process_index()}")
    return path


def _make_aio(aio_config, target_dir):
    """Shared AioHandle construction (LayerParamStore + HeteroLayerStore):
    thread sizing from the aio config, O_DIRECT when the filesystem
    supports it (DS_AIO_NO_ODIRECT=1 forces buffered)."""
    import os

    from ...ops.aio import AioHandle, o_direct_supported

    use_od = os.environ.get("DS_AIO_NO_ODIRECT") != "1" and \
        o_direct_supported(target_dir)
    ac = aio_config
    return AioHandle(
        num_threads=max(1, ac.thread_count if ac else 2),
        block_size=ac.block_size if ac else 1 << 20,
        queue_depth=ac.queue_depth if ac else 0,
        o_direct=use_od,
        single_submit=ac.single_submit if ac else False,
        overlap_events=ac.overlap_events if ac else True)


class _PackedWriteBuffers:
    """Double-buffered pack-and-write pair shared by both layer stores:
    packing layer i+1 overlaps the async write of layer i; the ticket for
    a half is drained only when that half is reused (or at flush)."""

    def __init__(self, aio, nbytes: int):
        from ...ops.aio import aligned_array

        self._aio = aio
        self._bufs = [aligned_array(nbytes) for _ in range(2)]
        self._tickets: List[Optional[int]] = [None, None]
        self._turn = 0

    def write(self, nbytes: int, fill, path: str) -> None:
        turn = self._turn
        if self._tickets[turn] is not None:
            self._aio.wait_ticket(self._tickets[turn])
            self._tickets[turn] = None
        buf = self._bufs[turn][:nbytes]
        fill(buf)
        self._tickets[turn] = self._aio.async_pwrite(buf, path)
        self._turn = 1 - turn

    def flush(self) -> None:
        for t, ticket in enumerate(self._tickets):
            if ticket is not None:
                self._aio.wait_ticket(ticket)
                self._tickets[t] = None


def check_supported(engine) -> None:
    """Fail at initialize() with actionable messages (mirrors the onebit
    wire's up-front validation). Round 5: model support went through the
    adapter registry (stream_adapters.make_adapter — TransformerLM +
    GPT2LMHeadModel) and dropout>0 is allowed (per-layer rng threading)."""
    from .stream_adapters import make_adapter

    make_adapter(engine.module, engine.compute_dtype)  # raises if unsupported
    opt_type = (engine._config.optimizer.type
                if engine._config.optimizer else "adam").lower()
    if opt_type not in ("adam", "adamw", "cpuadam"):
        raise ValueError(f"offload_param requires Adam/AdamW (got "
                         f"{opt_type!r}); the host step runs DeepSpeedCPUAdam")
    if engine.fp16_enabled:
        raise ValueError("offload_param streaming supports bf16/fp32 only "
                         "(no dynamic loss scaling on the host-step path); "
                         "use bf16 like the rest of the TPU stack")
    if engine.mp_world_size != 1 or \
            mesh_mod.get_sequence_parallel_world_size() > 1 or \
            mesh_mod.get_pipe_parallel_world_size() > 1:
        raise ValueError("offload_param streaming composes with data "
                         "parallelism only (mp=sp=pp=1)")
    # multi-process DP is supported (round 5): the per-layer grads carry a
    # replicated out-sharding over the GLOBAL mesh, so XLA's cross-replica
    # (cross-process) reduction runs before the D2H drain — every process
    # accumulates identical reduced grads and the per-process host Adam
    # stays in lockstep (asserted by tests/unit/comm/test_multiprocess.py)
    if engine._config.compression_training:
        raise ValueError("offload_param does not compose with compression "
                         "training (params are not device-resident)")


class LayerParamStore:
    """Host- or NVMe-resident scan-stacked block params served per layer as
    ONE packed byte buffer (the rotating-staging-buffer discipline of
    ``inference/zero_inference.py:_put_layer``, shared rationale documented
    there: pinned-transfer reuse, bounded RSS, no donation)."""

    def __init__(self, stacked_host, n_layer: int, compute_dtype,
                 device: OffloadDeviceEnum, nvme_dir: Optional[str] = None,
                 aio_config=None, prefetch: int = 1):
        self.n_layer = n_layer
        self.prefetch = max(0, prefetch)
        self.nvme = device == OffloadDeviceEnum.nvme
        self._dtype = np.dtype(compute_dtype)

        first = jax.tree_util.tree_map(lambda a: np.asarray(a[0]),
                                       stacked_host)
        self.wire = LayerWireFormat(first, compute_dtype)
        self.treedef = self.wire.treedef
        self.leaf_shapes = self.wire.shapes
        self.leaf_wire_dtypes = self.wire.wire_dtypes
        self.leaf_nbytes = self.wire.nbytes
        self.layer_nbytes = self.wire.total_nbytes

        n_slots = self.prefetch + 2
        self._staging: List[np.ndarray] = []
        self._staging_dev: List[Optional[jax.Array]] = [None] * n_slots
        self._aio = None
        if self.nvme:
            import os

            from ...ops.aio import aligned_array

            self.dir = _rank_dir(nvme_dir or "/tmp/ds_tpu_param_nvme")
            os.makedirs(self.dir, exist_ok=True)
            self._aio = _make_aio(aio_config, self.dir)
            # O_DIRECT-compatible staging buffers + the shared
            # double-buffered pack pair
            self._staging = [aligned_array(self.layer_nbytes)
                             for _ in range(n_slots)]
            self._packer = _PackedWriteBuffers(self._aio, self.layer_nbytes)
            self.stacked = None
            self._write_all_layers(stacked_host)
        else:
            self._staging = [np.empty(self.layer_nbytes, np.uint8)
                             for _ in range(n_slots)]
            self.stacked = _writable_tree(stacked_host)
        # streaming bookkeeping (begin_pass/next_layer)
        self._order: List[int] = []
        self._pos = 0
        self._tickets: Dict[int, Any] = {}
        self._slot_of: Dict[int, int] = {}

    # -- packing -------------------------------------------------------
    def _layer_file(self, i: int) -> str:
        import os

        return os.path.join(self.dir, f"layer_{i:05d}.bin")

    def _pack_into(self, layer_tree, buf: np.ndarray) -> None:
        self.wire.pack_into(layer_tree, buf)

    def write_layer(self, i: int, layer_tree) -> None:
        """Install ONE layer's new params (host arrays, wire dtypes).

        cpu tier: in-place row copy into the resident stacked tree (no new
        allocation). nvme tier: pack into the free half of the
        double-buffered pack pair and submit the file write — packing
        layer i+1 overlaps the write of layer i; call ``flush_writes``
        after the last layer."""
        if not self.nvme:
            for dst, src in zip(jax.tree_util.tree_leaves(self.stacked),
                                jax.tree_util.tree_leaves(layer_tree)):
                np.copyto(dst[i], np.asarray(src).astype(dst.dtype,
                                                         copy=False))
            return
        self._packer.write(self.layer_nbytes,
                           lambda buf: self._pack_into(layer_tree, buf),
                           self._layer_file(i))

    def flush_writes(self) -> None:
        if self.nvme:
            self._packer.flush()

    def _write_all_layers(self, stacked) -> None:
        """(Re)write every per-layer NVMe file from a stacked host tree
        (init / checkpoint-restore path; the training step streams
        per-layer via ``write_layer`` instead)."""
        for i in range(self.n_layer):
            layer = jax.tree_util.tree_map(lambda a: np.asarray(a[i]),
                                           stacked)
            self.write_layer(i, layer)
        self.flush_writes()

    def unpack(self, flat):
        """Traced: packed buffer -> layer param tree. Training wires are
        dtype-uniform, so the buffer ships TYPED and unpacks by
        slice+reshape (see LayerWireFormat.uniform_dtype for why the byte
        path is a real-TPU hazard)."""
        if self.wire.uniform_dtype is not None:
            return self.wire.unpack_typed(flat)
        return self.wire.unpack(flat)

    # -- streaming -----------------------------------------------------
    def begin_pass(self, order: List[int]) -> None:
        """Declare the exact layer visit order for the next pass (ascending
        for forward, descending for backward); read-ahead follows it."""
        assert not self._tickets, "previous pass not drained"
        self._order = list(order)
        self._pos = 0
        self._slot_of = {}
        if self.nvme:
            for j in range(min(self.prefetch + 1, len(self._order))):
                self._submit_read(j)

    def _submit_read(self, pos: int) -> None:
        i = self._order[pos]
        slot = pos % len(self._staging)
        prev = self._staging_dev[slot]
        if prev is not None:
            prev.block_until_ready()  # host buffer still feeding a transfer
            self._staging_dev[slot] = None
        self._slot_of[i] = slot
        self._tickets[i] = self._aio.async_pread(self._staging[slot],
                                                 self._layer_file(i))

    def next_layer(self):
        """(layer_index, packed device buffer) following the declared
        order; submits the next read-ahead (nvme) before returning."""
        pos = self._pos
        i = self._order[pos]
        self._pos += 1
        if self.nvme:
            slot = self._slot_of.pop(i)
            self._aio.wait_ticket(self._tickets.pop(i))
            nxt = pos + self.prefetch + 1
            if nxt < len(self._order):
                self._submit_read(nxt)
        else:
            slot = pos % len(self._staging)
            prev = self._staging_dev[slot]
            if prev is not None:
                prev.block_until_ready()
                self._staging_dev[slot] = None
            layer = jax.tree_util.tree_map(lambda a: np.asarray(a[i]),
                                           self.stacked)
            self._pack_into(layer, self._staging[slot])
        # release guard refs for landed transfers (device footprint stays
        # O(prefetch+1 layers)); runtimes without is_ready keep the refs
        for s, dev in enumerate(self._staging_dev):
            if dev is not None and s != slot:
                try:
                    if dev.is_ready():
                        self._staging_dev[s] = None
                except AttributeError:
                    break
        buf = self._staging[slot]
        uni = self.wire.uniform_dtype
        if uni is not None:
            buf = buf.view(uni)  # zero-copy typed view of the staging bytes
        payload = buf.copy() if jax.default_backend() == "cpu" else buf
        dev = jax.device_put(payload)
        self._staging_dev[slot] = dev
        return i, dev

    def update_from_stacked(self, new_stacked) -> None:
        """Install a full stacked host tree (checkpoint-restore path; the
        training step streams per-layer via ``write_layer`` instead)."""
        if self.nvme:
            self._write_all_layers(new_stacked)
        else:
            self.stacked = _writable_tree(new_stacked)

    def materialize_stacked(self):
        """Full stacked host tree (reads every NVMe layer file) — the
        checkpoint path."""
        if not self.nvme:
            return self.stacked
        from ...ops.aio import aligned_array

        out_leaves = [np.empty((self.n_layer,) + s, d) for s, d in
                      zip(self.leaf_shapes, self.leaf_wire_dtypes)]
        buf = aligned_array(self.layer_nbytes)
        for i in range(self.n_layer):
            self._aio.async_pread(buf, self._layer_file(i))
            self._aio.wait()
            layer = self.wire.unpack_host(buf)
            for leaf, lv in zip(out_leaves,
                                jax.tree_util.tree_leaves(layer)):
                leaf[i] = lv
        return jax.tree_util.tree_unflatten(self.treedef, out_leaves)


class HeteroLayerStore:
    """Per-layer param store for models whose layers DIFFER in structure
    (gpt_moe: alternating dense / MoE blocks — a Python loop, not
    ``nn.scan``). Same streaming discipline as :class:`LayerParamStore`
    (rotating staging slots, NVMe read-ahead, double-buffered writeback)
    with one :class:`LayerWireFormat` per layer KIND; ``next_layer``
    additionally yields the kind so the runner picks the matching jitted
    block function."""

    def __init__(self, layers_host: List, compute_dtype,
                 device: OffloadDeviceEnum, nvme_dir: Optional[str] = None,
                 aio_config=None, prefetch: int = 1):
        self.n_layer = len(layers_host)
        self.prefetch = max(0, prefetch)
        self.nvme = device == OffloadDeviceEnum.nvme

        # group layers by structural signature -> kinds
        self.kind_of: List[int] = []
        self.wires: List[LayerWireFormat] = []
        sig_to_kind: Dict[Any, int] = {}
        for tree in layers_host:
            leaves_wp, treedef = jax.tree_util.tree_flatten(tree)
            sig = (treedef, tuple((np.shape(a), str(np.asarray(a).dtype))
                                  for a in leaves_wp))
            if sig not in sig_to_kind:
                sig_to_kind[sig] = len(self.wires)
                self.wires.append(LayerWireFormat(tree, compute_dtype))
            self.kind_of.append(sig_to_kind[sig])
        self.max_nbytes = max(w.total_nbytes for w in self.wires)

        n_slots = self.prefetch + 2
        self._staging_dev: List[Optional[jax.Array]] = [None] * n_slots
        self._aio = None
        if self.nvme:
            import os

            from ...ops.aio import aligned_array

            self.dir = _rank_dir(nvme_dir or "/tmp/ds_tpu_param_nvme")
            os.makedirs(self.dir, exist_ok=True)
            self._aio = _make_aio(aio_config, self.dir)
            self._staging = [aligned_array(self.max_nbytes)
                             for _ in range(n_slots)]
            self._packer = _PackedWriteBuffers(self._aio, self.max_nbytes)
            self.layers = None
            for i, tree in enumerate(layers_host):
                self.write_layer(i, tree)
            self.flush_writes()
        else:
            self._staging = [np.empty(self.max_nbytes, np.uint8)
                             for _ in range(n_slots)]
            self.layers = [_writable_tree(t) for t in layers_host]
        self._order: List[int] = []
        self._pos = 0
        self._tickets: Dict[int, Any] = {}
        self._slot_of: Dict[int, int] = {}

    def _layer_file(self, i: int) -> str:
        import os

        return os.path.join(self.dir, f"layer_{i:05d}.bin")

    def unpack(self, kind: int, flat):
        w = self.wires[kind]
        if w.uniform_dtype is not None:
            return w.unpack_typed(flat)
        return w.unpack(flat)

    def begin_pass(self, order: List[int]) -> None:
        assert not self._tickets, "previous pass not drained"
        self._order = list(order)
        self._pos = 0
        self._slot_of = {}
        if self.nvme:
            for j in range(min(self.prefetch + 1, len(self._order))):
                self._submit_read(j)

    def _submit_read(self, pos: int) -> None:
        i = self._order[pos]
        slot = pos % len(self._staging)
        prev = self._staging_dev[slot]
        if prev is not None:
            prev.block_until_ready()
            self._staging_dev[slot] = None
        self._slot_of[i] = slot
        nbytes = self.wires[self.kind_of[i]].total_nbytes
        self._tickets[i] = self._aio.async_pread(
            self._staging[slot][:nbytes], self._layer_file(i))

    def next_layer(self):
        """(layer_index, kind, packed device buffer) in declared order."""
        pos = self._pos
        i = self._order[pos]
        kind = self.kind_of[i]
        w = self.wires[kind]
        self._pos += 1
        if self.nvme:
            slot = self._slot_of.pop(i)
            self._aio.wait_ticket(self._tickets.pop(i))
            nxt = pos + self.prefetch + 1
            if nxt < len(self._order):
                self._submit_read(nxt)
        else:
            slot = pos % len(self._staging)
            prev = self._staging_dev[slot]
            if prev is not None:
                prev.block_until_ready()
                self._staging_dev[slot] = None
            w.pack_into(self.layers[i], self._staging[slot][:w.total_nbytes])
        for s, dev in enumerate(self._staging_dev):
            if dev is not None and s != slot:
                try:
                    if dev.is_ready():
                        self._staging_dev[s] = None
                except AttributeError:
                    break
        buf = self._staging[slot][:w.total_nbytes]
        if w.uniform_dtype is not None:
            buf = buf.view(w.uniform_dtype)
        payload = buf.copy() if jax.default_backend() == "cpu" else buf
        dev = jax.device_put(payload)
        self._staging_dev[slot] = dev
        return i, kind, dev

    def write_layer(self, i: int, layer_tree) -> None:
        if not self.nvme:
            for dst, src in zip(jax.tree_util.tree_leaves(self.layers[i]),
                                jax.tree_util.tree_leaves(layer_tree)):
                np.copyto(dst, np.asarray(src).astype(dst.dtype, copy=False))
            return
        w = self.wires[self.kind_of[i]]
        self._packer.write(w.total_nbytes,
                           lambda buf: w.pack_into(layer_tree, buf),
                           self._layer_file(i))

    def flush_writes(self) -> None:
        if self.nvme:
            self._packer.flush()

    def materialize_layers(self) -> List:
        """All layers as host trees (checkpoint surface)."""
        if not self.nvme:
            return list(self.layers)
        from ...ops.aio import aligned_array

        out = []
        buf = aligned_array(self.max_nbytes)
        for i in range(self.n_layer):
            w = self.wires[self.kind_of[i]]
            t = self._aio.async_pread(buf[:w.total_nbytes],
                                      self._layer_file(i))
            self._aio.wait_ticket(t)
            out.append(w.unpack_host(buf[:w.total_nbytes]))
        return out


class GradRowStore:
    """Per-layer gradient accumulation for the streamed backward.

    dram mode: fp32 row arrays per (leaf, layer), freed per layer by the
    finalize. nvme mode (the full ZeRO-Infinity grad tier,
    ``swap_tensor``'s gradient swap analog): each layer's packed fp32 grad
    rows live in ONE file; accumulation is read-modify-write per micro
    batch and the per-layer sum-of-squares is captured on the LAST micro,
    so the global-norm clip never needs the whole grad tree in DRAM —
    host memory stays O(layer) for the entire step."""

    def __init__(self, n_layer: int, leaf_shapes, nvme_dir: Optional[str],
                 aio=None, per_layer_shapes=None):
        """``leaf_shapes``: shared per-layer leaf shapes (scan-stacked
        models); ``per_layer_shapes`` overrides with one shape list PER
        layer (heterogeneous models, e.g. alternating dense/MoE blocks)."""
        self.n_layer = n_layer
        if per_layer_shapes is None:
            per_layer_shapes = [list(leaf_shapes)] * n_layer
        self._layer_shapes = [list(s) for s in per_layer_shapes]
        self._layer_sizes = [[int(np.prod(s)) if s else 1 for s in shapes]
                             for shapes in self._layer_shapes]
        self._layer_offsets = [np.cumsum([0] + sizes)
                               for sizes in self._layer_sizes]
        self._layer_total = [int(off[-1]) for off in self._layer_offsets]
        self.nvme = nvme_dir is not None
        self.sq: Dict[int, float] = {}
        if self.nvme:
            import os

            from ...ops.aio import aligned_array

            self.dir = os.path.join(nvme_dir, "grads")
            os.makedirs(self.dir, exist_ok=True)
            self._aio = aio
            self._buf = aligned_array(
                max(self._layer_total) * 4).view(np.float32)
            self._have: set = set()
        else:
            self.rows: Dict[int, Optional[np.ndarray]] = {}

    def _file(self, li: int) -> str:
        import os

        return os.path.join(self.dir, f"grad_{li:05d}.bin")

    def _pack(self, li: int, leaves, out: np.ndarray) -> None:
        for off, size, leaf in zip(self._layer_offsets[li],
                                   self._layer_sizes[li], leaves):
            out[off:off + size] = np.asarray(leaf, np.float32).ravel()

    def accumulate(self, li: int, leaves, is_last: bool) -> None:
        """Add one micro batch's fp32 grad rows for layer ``li``; on the
        last micro also record the layer's sum of squares."""
        total = self._layer_total[li]
        if not self.nvme:
            flat = self.rows.get(li)
            if flat is None:
                flat = np.empty(total, np.float32)
                self._pack(li, leaves, flat)
                self.rows[li] = flat
            else:
                for off, size, leaf in zip(self._layer_offsets[li],
                                           self._layer_sizes[li], leaves):
                    flat[off:off + size] += np.asarray(
                        leaf, np.float32).ravel()
            if is_last:
                self.sq[li] = float(np.dot(flat, flat))
            return
        # per-ticket waits only: the AioHandle is SHARED with
        # LayerParamStore — a handle-global wait() here would drain the
        # store's in-flight layer prefetches / pack writes and serialize
        # the streaming pipeline
        buf = self._buf[:total]
        if li in self._have:
            t = self._aio.async_pread(buf, self._file(li))
            self._aio.wait_ticket(t)
            for off, size, leaf in zip(self._layer_offsets[li],
                                       self._layer_sizes[li], leaves):
                buf[off:off + size] += np.asarray(
                    leaf, np.float32).ravel()
        else:
            self._pack(li, leaves, buf)
            self._have.add(li)
        if is_last:
            self.sq[li] = float(np.dot(buf, buf))
        t = self._aio.async_pwrite(buf, self._file(li))
        self._aio.wait_ticket(t)

    def total_sq(self) -> float:
        return float(sum(self.sq.values()))

    def read_rows(self, li: int):
        """The layer's accumulated fp32 rows (leaf-shaped views)."""
        if not self.nvme:
            flat = self.rows[li]
        else:
            flat = self._buf[:self._layer_total[li]]
            t = self._aio.async_pread(flat, self._file(li))
            self._aio.wait_ticket(t)  # shared handle: no global wait
        return [flat[off:off + size].reshape(shape)
                for off, size, shape in zip(self._layer_offsets[li],
                                            self._layer_sizes[li],
                                            self._layer_shapes[li])]

    def free(self, li: int) -> None:
        if not self.nvme:
            self.rows[li] = None
        # nvme: the file is simply overwritten next step

    def reset(self) -> None:
        self.sq = {}
        if self.nvme:
            self._have = set()
        else:
            self.rows = {}


class ParamOffloadRunner:
    """The engine's ``offload_param`` training path: streamed forward /
    backward over :class:`LayerParamStore` + host :class:`OffloadedOptimizer`
    step. Driven by ``DeepSpeedEngine.train_batch``."""

    RESIDENT_KEYS = ("embed_tokens", "embed_pos", "embed_ln", "ln_f",
                     "lm_head")

    def __init__(self, engine, params_host):
        from .stream_adapters import make_adapter

        check_supported(engine)
        self.engine = engine
        cfg = engine.module.config
        self.cfg = cfg
        self.mesh = engine.mesh
        self.compute_dtype = engine.compute_dtype
        self.adapter = make_adapter(engine.module, engine.compute_dtype)
        self.clip = engine.gradient_clipping()
        self.gas = engine.gradient_accumulation_steps()
        self.op_cfg = engine.zero_config.offload_param
        self._base_rng = jax.random.PRNGKey(
            getattr(engine._config, "seed", 1234) or 1234)

        params_host = jax.tree_util.tree_map(lambda a: np.asarray(a),
                                             params_host)
        self._treedef = jax.tree_util.tree_structure(params_host)
        # canonical flat paths (must match OffloadedOptimizer's keys)
        self._all_keys = list(_flatten_with_paths(params_host).keys())

        # host optimizer over the FULL tree (resident + stacked) — master
        # placement per offload_optimizer config (default: host DRAM)
        oo = engine.zero_config.offload_optimizer
        if oo is None or oo.device == OffloadDeviceEnum.none:
            from .offload_config import DeepSpeedZeroOffloadOptimizerConfig

            oo = DeepSpeedZeroOffloadOptimizerConfig(device="cpu")
        opt_cfg = engine._config.optimizer
        opt_params = dict(opt_cfg.params if opt_cfg else {})
        opt_params.setdefault("lr", engine._base_lr)
        self.opt = OffloadedOptimizer(params_host, opt_params, oo,
                                      aio_config=engine._config.aio)

        # split the tree: resident (device) vs streamed (store)
        self.hetero = getattr(self.adapter, "heterogeneous", False)
        self.has_aux = getattr(self.adapter, "has_aux", False)
        self._resident_host, streamed = self.adapter.split(params_host)
        if self.hetero:
            self.store = HeteroLayerStore(
                streamed, self.compute_dtype, self.op_cfg.device,
                nvme_dir=self.op_cfg.nvme_path,
                aio_config=engine._config.aio,
                prefetch=max(1, min(self.op_cfg.buffer_count - 1, 4)))
        else:
            self.store = LayerParamStore(
                streamed, cfg.n_layer, self.compute_dtype,
                self.op_cfg.device, nvme_dir=self.op_cfg.nvme_path,
                aio_config=engine._config.aio,
                prefetch=max(1, min(self.op_cfg.buffer_count - 1, 4)))

        rep = NamedSharding(self.mesh, PartitionSpec())
        self._rep = rep
        batch_axes = tuple(mesh_mod.batch_axes())
        self._data_sh = NamedSharding(self.mesh, PartitionSpec(batch_axes))

        def to_dev(tree):
            def put(a):
                a = np.asarray(a)
                if jnp.issubdtype(a.dtype, jnp.floating):
                    a = a.astype(self.compute_dtype)
                return jax.device_put(a, rep)

            return jax.tree_util.tree_map(put, tree)

        self.resident = to_dev(self._resident_host)

        adapter = self.adapter

        # ---- jitted pieces (each reused for every layer/micro) --------
        if self.hetero:
            self._build_hetero_block_fns(rep)
        else:
            unpack = self.store.unpack

            def block_fwd(packed, x, rng):
                return adapter.block_apply(unpack(packed), x, rng)

            self._jit_block_fwd = jax.jit(
                block_fwd, out_shardings=self._data_sh)

            def block_fwd_eval(packed, x, rng):
                return adapter.block_apply(unpack(packed), x, rng,
                                           deterministic=True)

            self._jit_block_fwd_eval = jax.jit(
                block_fwd_eval, out_shardings=self._data_sh)

            def block_bwd(packed, x, dy, rng):
                layer = unpack(packed)

                def f(lp, xi):
                    return adapter.block_apply(lp, xi, rng)

                _, vjp = jax.vjp(f, layer, x)
                dlayer, dx = vjp(dy)
                return dx, dlayer

            grad_rep = jax.tree_util.tree_map(
                lambda _: rep,
                jax.tree_util.tree_unflatten(
                    self.store.treedef,
                    [0] * len(self.store.leaf_shapes)))
            self._jit_block_bwd = jax.jit(
                block_bwd, out_shardings=(self._data_sh, grad_rep))

        def embed_fwd(resident, batch):
            return adapter.embed_apply(resident, batch)

        self._jit_embed = jax.jit(embed_fwd, out_shardings=self._data_sh)

        head_loss = adapter.head_loss

        def head_bwd(resident, xL, batch):
            (loss, (dres, dx)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(resident, xL, batch)
            return loss, dres, dx

        res_rep = jax.tree_util.tree_map(lambda _: rep, self.resident)
        self._jit_head_bwd = jax.jit(
            head_bwd, out_shardings=(rep, res_rep, self._data_sh))
        # loss-only head for evaluation: no value_and_grad over the
        # resident tree (eval_loss must not pay the head
        # backward + gradient buffers)
        self._jit_head_loss = jax.jit(head_loss, out_shardings=rep)

        def embed_bwd(resident, batch, dx0, dres_head):
            _, vjp = jax.vjp(lambda r: embed_fwd(r, batch), resident)
            (dres,) = vjp(dx0.astype(self.compute_dtype))
            return jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) + b.astype(jnp.float32),
                dres, dres_head)

        res_rep32 = jax.tree_util.tree_map(lambda _: rep, self.resident)
        self._jit_embed_bwd = jax.jit(embed_bwd, out_shardings=res_rep32)

        self._acc_add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x + y, a, b))

        # per-layer grad accumulation: DRAM rows (cpu tier) or per-layer
        # NVMe files (nvme tier — the ZeRO-Infinity gradient-swap analog,
        # O(layer) host DRAM for the whole step)
        if self.hetero:
            self.grads = GradRowStore(
                self.store.n_layer, None,
                self.store.dir if self.store.nvme else None,
                aio=self.store._aio,
                per_layer_shapes=[
                    self.store.wires[k].shapes for k in self.store.kind_of])
        else:
            self.grads = GradRowStore(
                self.store.n_layer, self.store.leaf_shapes,
                self.store.dir if self.store.nvme else None,
                aio=self.store._aio)
        self.last_timings: Dict[str, float] = {}
        nbytes = self.store.max_nbytes if self.hetero \
            else self.store.layer_nbytes
        log_dist(
            f"ZeRO param offload: device={self.op_cfg.device} "
            f"{cfg.n_layer} layers x {nbytes / 1e6:.1f} MB streamed, "
            f"optimizer={'nvme' if self.opt.nvme else 'cpu'}"
            + ("" if self.opt.swap_master or not self.opt.nvme
               else " (moments-only swap)"), ranks=[0])

    # -- helpers -------------------------------------------------------
    def _build_hetero_block_fns(self, rep_sharding):
        """One jitted fwd/bwd/eval per structural KIND (dense vs each MoE
        shape) — layers of the same kind share the compiled program. Block
        outputs are ``(x, aux)``; the bwd vjp receives ``aux_weight`` as
        the aux cotangent so router grads match the resident engine."""
        adapter = self.adapter
        store = self.store
        aux_ct = jnp.asarray(getattr(adapter, "aux_weight", 0.0),
                             jnp.float32)
        rep_layer = {}
        for i, k in enumerate(store.kind_of):
            rep_layer.setdefault(k, i)
        self._jit_block_fwd_k = {}
        self._jit_block_fwd_eval_k = {}
        self._jit_block_bwd_k = {}
        for k, ri in rep_layer.items():
            def fwd(packed, x, rng, _k=k, _ri=ri):
                return adapter.block_apply_layer(
                    _ri, store.unpack(_k, packed), x, rng)

            def fwd_eval(packed, x, rng, _k=k, _ri=ri):
                return adapter.block_apply_layer(
                    _ri, store.unpack(_k, packed), x, rng,
                    deterministic=True)

            def bwd(packed, x, dy, rng, _k=k, _ri=ri):
                layer = store.unpack(_k, packed)

                def f(lp, xi):
                    return adapter.block_apply_layer(_ri, lp, xi, rng)

                _, vjp = jax.vjp(f, layer, x)
                dlayer, dx = vjp((dy, aux_ct))
                return dx, dlayer

            grad_rep = jax.tree_util.tree_map(
                lambda _: rep_sharding,
                jax.tree_util.tree_unflatten(
                    store.wires[k].treedef,
                    [0] * len(store.wires[k].shapes)))
            self._jit_block_fwd_k[k] = jax.jit(
                fwd, out_shardings=(self._data_sh, rep_sharding))
            self._jit_block_fwd_eval_k[k] = jax.jit(
                fwd_eval, out_shardings=(self._data_sh, rep_sharding))
            self._jit_block_bwd_k[k] = jax.jit(
                bwd, out_shardings=(self._data_sh, grad_rep))

    def _layer_paths(self, i: int):
        """Canonical flat param paths of heterogeneous layer ``i``."""
        kind = self.store.kind_of[i]
        w = self.store.wires[kind]
        leaves_wp, _ = jax.tree_util.tree_flatten_with_path(
            jax.tree_util.tree_unflatten(w.treedef,
                                         list(range(len(w.shapes)))))
        prefix = self.adapter.layer_key(i) + "/"
        return [prefix + _path_str(p) for p, _ in leaves_wp]

    def _stacked_paths(self):
        """Canonical flat path prefix for stacked leaves."""
        leaves_wp, _ = jax.tree_util.tree_flatten_with_path(
            jax.tree_util.tree_unflatten(
                self.store.treedef, list(range(len(self.store.leaf_shapes)))))
        return ["blocks/block/" + _path_str(p) for p, _ in leaves_wp]


    # -- the step ------------------------------------------------------
    def train_batch(self, micro_batches) -> Dict[str, Any]:
        """One global step over ``gas`` micro batches (host numpy trees).
        Returns the engine-shaped metrics dict."""
        t0 = time.perf_counter()
        self.grads.reset()
        L = self.store.n_layer
        stacked_paths = None if self.hetero else self._stacked_paths()
        aux_sum = 0.0
        res_grad_acc = None
        loss_sum = 0.0
        t_fwd = t_bwd = 0.0
        eng = self.engine
        # per-(micro, layer) dropout keys, one device op per step; numpy
        # rows feed the jitted block fns (same key for fwd and bwd vjp so
        # the recompute sees identical masks)
        step_rng = jax.random.fold_in(self._base_rng, eng.global_steps)
        np_keys = np.asarray(jax.random.split(
            step_rng, max(1, len(micro_batches)) * L)).reshape(
                max(1, len(micro_batches)), L, -1)

        for mi, mb in enumerate(micro_batches):
            mb = jax.tree_util.tree_map(
                lambda a: jax.device_put(np.asarray(a), self._data_sh), mb)
            tf0 = time.perf_counter()
            x = self._jit_embed(self.resident, mb)
            acts = [x]
            micro_aux = []  # device scalars; fetched with the loss below
            self.store.begin_pass(list(range(L)))
            for li in range(L):
                if self.hetero:
                    _, kind, packed = self.store.next_layer()
                    x, aux = self._jit_block_fwd_k[kind](
                        packed, x, np_keys[mi, li])
                    if self.has_aux:
                        micro_aux.append(aux)
                else:
                    _, packed = self.store.next_layer()
                    x = self._jit_block_fwd(packed, x, np_keys[mi, li])
                acts.append(x)
            loss, dres_head, dy = self._jit_head_bwd(
                self.resident, acts[-1], mb)
            t_fwd += time.perf_counter() - tf0

            tb0 = time.perf_counter()
            is_last = mi == len(micro_batches) - 1
            pending = deque()  # (layer, dlayer) with D2H in flight
            self.store.begin_pass(list(range(L - 1, -1, -1)))
            for li in range(L - 1, -1, -1):
                if self.hetero:
                    _, kind, packed = self.store.next_layer()
                    dy, dlayer = self._jit_block_bwd_k[kind](
                        packed, acts[li], dy, np_keys[mi, li])
                else:
                    _, packed = self.store.next_layer()
                    dy, dlayer = self._jit_block_bwd(packed, acts[li], dy,
                                                     np_keys[mi, li])
                acts[li + 1] = None  # free the boundary activation
                for g in jax.tree_util.tree_leaves(dlayer):
                    g.copy_to_host_async()
                pending.append((li, dlayer))
                if len(pending) > 1:
                    self._drain_grad(pending.popleft(), is_last)
            while pending:
                self._drain_grad(pending.popleft(), is_last)
            dres = self._jit_embed_bwd(
                self.resident, mb, dy, dres_head)
            res_grad_acc = dres if res_grad_acc is None else \
                self._acc_add(res_grad_acc, dres)
            loss_sum += float(loss)
            if self.has_aux and micro_aux:
                # engine tuple-return convention: metric = loss + w * aux;
                # sum on device (scalar adds), ONE host fetch per micro
                aux_dev = micro_aux[0]
                for a in micro_aux[1:]:
                    aux_dev = self._acc_add(aux_dev, a)
                aux_sum += self.adapter.aux_weight * float(aux_dev)
            acts = None
            t_bwd += time.perf_counter() - tb0

        # ---- finalize: norm, clip, host Adam, store update ------------
        # Layer-streamed: resident leaves go
        # through the pipelined whole-leaf step; the stacked trunk updates
        # one LAYER at a time (per-row Adam via step_rows, write_layer
        # writeback, grad rows freed as they land) — the full new param
        # tree never materializes in host DRAM.
        t2 = time.perf_counter()
        res_host = jax.device_get(res_grad_acc)
        res_flat = {k: np.asarray(v, np.float32) for k, v in
                    _flatten_with_paths(res_host).items()}
        inv_gas = 1.0 / float(self.gas)
        sq = self.grads.total_sq()
        for a in res_flat.values():
            flat = a.reshape(-1)
            sq += float(np.dot(flat, flat))
        grad_norm = float(np.sqrt(sq)) * inv_gas
        scale = inv_gas
        if self.clip > 0 and grad_norm > self.clip:
            scale *= self.clip / (grad_norm + 1e-6)

        lr = float(eng._lr_fn(jnp.asarray(eng.global_steps)))
        step_num = eng.global_steps + 1
        new_res_flat = self.opt.step(
            res_flat, lr, step_num, np.dtype(self.compute_dtype),
            grad_scale=scale, release_grads=True,
            keys=set(res_flat.keys()))
        self._resident_host = _unflatten_like(
            self._resident_host, new_res_flat)
        self.resident = jax.tree_util.tree_map(
            lambda a: jax.device_put(np.asarray(a), self._rep),
            self._resident_host)
        t3 = time.perf_counter()

        for li in range(L):
            rows = self.grads.read_rows(li)
            if self.hetero:
                # per-layer param subtrees: whole-leaf pipelined step over
                # just this layer's keys (same O(layer) discipline)
                paths = self._layer_paths(li)
                new_flat = self.opt.step(
                    dict(zip(paths, rows)), lr, step_num,
                    np.dtype(self.compute_dtype), grad_scale=scale,
                    release_grads=True, keys=set(paths))
                self.grads.free(li)
                kind = self.store.kind_of[li]
                self.store.write_layer(li, jax.tree_util.tree_unflatten(
                    self.store.wires[kind].treedef,
                    [new_flat[p] for p in paths]))
                continue
            new_rows = [
                self.opt.step_rows(path, li, row, lr, step_num,
                                   np.dtype(self.compute_dtype),
                                   grad_scale=scale)
                for path, row in zip(stacked_paths, rows)]
            self.opt.drain_row_writes()  # one drain per layer, not per row
            self.grads.free(li)
            self.store.write_layer(li, jax.tree_util.tree_unflatten(
                self.store.treedef, new_rows))
        self.store.flush_writes()
        self.opt.drain_row_writes()
        t4 = time.perf_counter()

        self.last_timings = {
            "forward_stream_s": t_fwd, "backward_stream_s": t_bwd,
            "grad_finalize_s": t2 - t0 - t_fwd - t_bwd,
            "host_adam_s": t3 - t2,  # resident leaves (pipelined step)
            # stacked trunk: per-layer Adam + writeback, streamed
            "param_writeback_s": t4 - t3,
            **{f"adam_{k}": v for k, v in
               getattr(self.opt, "last_timings", {}).items()},
        }
        return {
            "loss": (loss_sum + aux_sum) * inv_gas,
            "grad_norm": grad_norm,
            "lr": lr,
            "overflow": False,
            "loss_scale": 1.0,
        }

    def _drain_grad(self, item, is_last: bool) -> None:
        li, dlayer = item
        self.grads.accumulate(
            li, jax.tree_util.tree_leaves(dlayer), is_last)

    # -- eval / checkpoint surface -------------------------------------
    def eval_loss(self, batch) -> float:
        """Streamed forward + loss (no grads) — evaluation under offload.
        Uses the loss-only head jit (no resident backward / grad buffers)
        and deterministic blocks (eval keys are unused when dropout=0 and
        fixed when dropout>0 — evaluation never drops, matching the
        resident engine's eval_batch)."""
        mb = jax.tree_util.tree_map(
            lambda a: jax.device_put(np.asarray(a), self._data_sh), batch)
        x = self._jit_embed(self.resident, mb)
        L = self.store.n_layer
        zero_key = np.zeros_like(
            np.asarray(jax.random.PRNGKey(0)))
        aux_dev = None
        self.store.begin_pass(list(range(L)))
        for _ in range(L):
            if self.hetero:
                _, kind, packed = self.store.next_layer()
                x, aux = self._jit_block_fwd_eval_k[kind](packed, x,
                                                          zero_key)
                if self.has_aux:
                    aux_dev = aux if aux_dev is None else \
                        self._acc_add(aux_dev, aux)
            else:
                _, packed = self.store.next_layer()
                x = self._jit_block_fwd_eval(packed, x, zero_key)
        loss = float(self._jit_head_loss(self.resident, x, mb))
        if aux_dev is not None:
            loss += self.adapter.aux_weight * float(aux_dev)
        return loss

    def full_params_tree(self):
        """The complete param pytree as host arrays (checkpoint surface;
        materializes the NVMe store)."""
        if self.hetero:
            tree = self.adapter.merge(self._resident_host,
                                      self.store.materialize_layers())
        else:
            tree = self.adapter.merge(self._resident_host,
                                      self.store.materialize_stacked())
        # restore original key order via the saved treedef
        flat = _flatten_with_paths(tree)
        return jax.tree_util.tree_unflatten(
            self._treedef, [flat[k] for k in self._all_keys])

    def load_params(self, params_host) -> None:
        """Install externally-loaded params (checkpoint restore); the
        caller is responsible for optimizer state (engine handles it via
        sync_master_from / load_state_dict, same as the resident path)."""
        params_host = jax.tree_util.tree_map(lambda a: np.asarray(a),
                                             params_host)
        self._resident_host, streamed = self.adapter.split(params_host)
        self.resident = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                a.astype(self.compute_dtype) if jnp.issubdtype(
                    a.dtype, jnp.floating) else a, self._rep),
            self._resident_host)
        if self.hetero:
            for i, tree in enumerate(streamed):
                self.store.write_layer(i, tree)
            self.store.flush_writes()
        else:
            self.store.update_from_stacked(streamed)
