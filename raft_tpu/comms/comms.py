"""Communicator facade — analog of ``raft::comms::comms_t``
(cpp/include/raft/core/comms.hpp:108-630: allreduce, bcast, reduce,
allgather(v), gather(v), reducescatter, isend/irecv, device_send/recv/
sendrecv, device_multicast_sendrecv, barrier, sync_stream, comm_split) and
its NCCL/UCX/MPI backends (comms/detail/std_comms.hpp:55-533,
detail/mpi_comms.hpp:77-440).

TPU mapping: collectives are XLA ops over a named mesh axis inside
``shard_map`` — ICI within a slice, DCN across slices, chosen by the
compiler from the mesh layout. :class:`AxisComms` is the device-side typed
facade (usable only inside a ``shard_map``-traced function, the SPMD region
that replaces the reference's per-rank CUDA stream context). The host-side
bootstrap — the reference's Dask + NCCL-uniqueId rendezvous
(python/raft/raft/dask/common/comms.py:37-244) — reduces to
``jax.distributed.initialize`` + mesh construction (:class:`Comms`).

Collectives ride:
    allreduce       -> lax.psum / pmax / pmin
    bcast           -> psum of the root's masked shard
    reduce          -> allreduce + root-only validity (SPMD keeps shapes)
    allgather       -> lax.all_gather
    allgatherv      -> all_gather over padded max-size slots (static shapes)
    gather/gatherv  -> allgather + root-only validity
    reducescatter   -> lax.psum_scatter
    device_sendrecv -> lax.ppermute (tagged p2p ≈ explicit permutation pairs)
    barrier         -> psum of a zero scalar
    comm_split      -> host-level sub-mesh construction (new AxisComms name)
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import compat, errors

__all__ = [
    "ReduceOp", "AxisComms", "P2PBatch", "Comms", "HierarchicalComms",
    "build_comms", "build_comms_hierarchical", "inject_comms",
]


class ReduceOp(enum.Enum):
    """Mirror of ``raft::comms::op_t`` (core/comms.hpp:81-87)."""

    SUM = "sum"
    PROD = "prod"
    MIN = "min"
    MAX = "max"


def _resolve_op(op) -> ReduceOp:
    if isinstance(op, ReduceOp):
        return op
    return ReduceOp(str(op).lower())


@dataclasses.dataclass(frozen=True)
class AxisComms:
    """Typed collective API over one named mesh axis; every method must be
    called from inside a ``shard_map`` over that axis (the reference's
    "inside a rank" context). Analog of ``comms_t`` (core/comms.hpp:108)."""

    axis: str

    # -- topology ------------------------------------------------------------
    def get_size(self) -> int:
        return compat.axis_size(self.axis)

    def get_rank(self):
        return lax.axis_index(self.axis)

    # -- collectives -----------------------------------------------------------
    def allreduce(self, x, op=ReduceOp.SUM):
        op = _resolve_op(op)
        if op == ReduceOp.SUM:
            return lax.psum(x, self.axis)
        if op == ReduceOp.MAX:
            return lax.pmax(x, self.axis)
        if op == ReduceOp.MIN:
            return lax.pmin(x, self.axis)
        # PROD via log-space is lossy; use exp(psum(log)) only for positive
        # inputs — do it the robust way with all_gather + prod reduce.
        g = lax.all_gather(x, self.axis)
        return jnp.prod(g, axis=0)

    def bcast(self, x, root: int = 0):
        """Every rank receives root's ``x`` (comms.hpp:208 one-buffer bcast)."""
        me = self.get_rank()
        masked = jnp.where(me == root, x, jnp.zeros_like(x))
        return lax.psum(masked, self.axis)

    def reduce(self, x, root: int = 0, op=ReduceOp.SUM):
        """SPMD note: every rank computes the reduction (shapes are uniform
        under shard_map); only root's copy is semantically valid, matching
        the reference contract (comms.hpp:253)."""
        return self.allreduce(x, op)

    def allgather(self, x, axis: int = 0, tiled: bool = False):
        """Concatenate every rank's shard along ``axis``
        (comms.hpp:299 allgather)."""
        return lax.all_gather(x, self.axis, axis=axis, tiled=True) if tiled \
            else lax.all_gather(x, self.axis, axis=axis)

    def allgatherv(self, x, valid_count, max_count: int):
        """Variable-size allgather (comms.hpp:320). Static-shape TPU form:
        each rank contributes a (max_count, ...) slot plus its valid count;
        returns (stacked (size, max_count, ...), counts (size,))."""
        errors.expects(
            x.shape[0] <= max_count,
            "allgatherv: contribution has %d rows > max_count=%d — every "
            "rank's slot is padded TO max_count, it cannot shrink to it",
            x.shape[0], max_count,
        )
        pad = [(0, max_count - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
        slot = jnp.pad(x, pad)
        return (
            lax.all_gather(slot, self.axis),
            lax.all_gather(valid_count, self.axis),
        )

    def gather(self, x, root: int = 0, axis: int = 0):
        """comms.hpp:352; SPMD: all ranks hold the result, root's is valid."""
        return self.allgather(x, axis=axis)

    def gatherv(self, x, valid_count, max_count: int, root: int = 0):
        return self.allgatherv(x, valid_count, max_count)

    def reducescatter(self, x, op=ReduceOp.SUM, tiled: bool = False):
        """Each rank gets its slice of the reduction (comms.hpp:401)."""
        op = _resolve_op(op)
        sz = self.get_size()
        errors.expects(
            x.shape[0] % sz == 0,
            "reducescatter: leading dim %d not divisible by the "
            "communicator size %d — each rank's slice must be uniform",
            x.shape[0], sz,
        )
        if op != ReduceOp.SUM:
            g = self.allreduce(x, op)
            shard = x.shape[0] // sz
            return lax.dynamic_slice_in_dim(g, self.get_rank() * shard, shard)
        return lax.psum_scatter(x, self.axis, tiled=tiled)

    # -- p2p -------------------------------------------------------------------
    def sendrecv(self, x, perm: Sequence[Tuple[int, int]]):
        """Explicit (src, dst) pair exchange — the structured analog of the
        reference's tagged isend/irecv + device_sendrecv (comms.hpp:440-570,
        ucp p2p std_comms.hpp:264-533). Ranks not named as a destination
        receive zeros."""
        return lax.ppermute(x, self.axis, perm)

    def ring_shift(self, x, shift: int = 1):
        """Ring permute — the building block for ring-style dataflow
        (out-of-HBM kNN, ring attention analogs)."""
        n = self.get_size()
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, self.axis, perm)

    def alltoall(self, x):
        """Each rank's ``x`` (size, chunk, ...) scatters chunk ``j`` to
        rank ``j``; the result's slot ``s`` holds the chunk rank ``s``
        sent here — ncclAllToAll / MPI_Alltoall shape (the reference
        composes it from grouped p2p sends, std_comms.hpp:264-463; on TPU
        it is one ICI-routed ``lax.all_to_all``). The row-exchange
        backbone of the distributed index build (mnmg_ivf.py)."""
        return lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0)

    def p2p_batch(self) -> "P2PBatch":
        """Deferred tagged point-to-point batch — the analog of the
        reference's ``isend``/``irecv``/``waitall`` (core/comms.hpp:440-508,
        UCX-tagged in std_comms.hpp:264-463). See :class:`P2PBatch`."""
        return P2PBatch(self)

    def device_multicast_sendrecv(self, x, sources: Sequence[int], dest: int):
        """comms.hpp:570: gather several sources' buffers at ``dest``; SPMD
        form returns the stacked sources on every rank."""
        g = lax.all_gather(x, self.axis)
        return g[jnp.asarray(sources)]

    # -- control ---------------------------------------------------------------
    def barrier(self):
        """comms.hpp:170: collectively synchronise — a zero psum forces a
        cross-replica dependency."""
        return lax.psum(jnp.zeros((), jnp.int32), self.axis)

    def sync_stream(self):
        """No-op on TPU: XLA owns scheduling; status propagation is via the
        computation's own error semantics (reference std_comms sync_stream
        polls NCCL async errors)."""
        return None


class P2PBatch:
    """Tagged, deferred point-to-point transfers over a mesh axis.

    The reference records nonblocking ``isend``/``irecv`` requests and
    completes them in ``waitall`` (core/comms.hpp:440-508; UCX tags,
    std_comms.hpp:264-463). SPMD under XLA traces one program for every
    rank, so the pattern is declared collectively: every rank records the
    SAME (src, dst, tag) entries, each passing its local candidate value;
    ``waitall`` batches each tag's pairs into the minimum number of
    ``ppermute`` rounds (splitting when a source or destination repeats
    within a tag — the "multiple in-flight transfers" the reference's tags
    exist for) and returns the delivered arrays keyed by (src, dst, tag).

    Usage (inside shard_map):
        p2p = comms.p2p_batch()
        p2p.isend(my_block, src=0, dest=3, tag=0)
        p2p.irecv(src=0, dest=3, tag=0)
        got = p2p.waitall()[(0, 3, 0)]   # my_block of rank 0 on rank 3

    A rank that is not the destination of a transfer reads zeros for it
    (ppermute semantics) — callers mask by ``get_rank()`` exactly as
    reference callers guard on ``comm.get_rank()``.
    """

    def __init__(self, comms: AxisComms):
        self._comms = comms
        self._sends = []   # (src, dst, tag, value)
        self._recvs = []   # (src, dst, tag)

    def isend(self, x, src: int, dest: int, tag: int = 0) -> None:
        errors.expects(src != dest, "p2p: src == dest == %d", src)
        self._sends.append((int(src), int(dest), int(tag), jnp.asarray(x)))

    def irecv(self, src: int, dest: int, tag: int = 0) -> Tuple[int, int, int]:
        key = (int(src), int(dest), int(tag))
        self._recvs.append(key)
        return key

    def waitall(self):
        """Execute all recorded transfers; returns {(src, dst, tag): array}.

        Validates the send/recv sets match, as the reference's waitall
        contract implies (an unmatched tag hangs a UCX endpoint; here it
        is an immediate error). A validation failure CLEARS the recorded
        state (as completion does), so a corrected retry on the same
        batch records from scratch instead of colliding with the stale
        entries of the rejected attempt."""
        try:
            send_keys = [(s, d, t) for s, d, t, _ in self._sends]
            sends = set(send_keys)
            recvs = set(self._recvs)
            # duplicate (src, dst, tag) keys are ambiguous — the result
            # dict could only hold one of them (the UCX reference
            # disambiguates by distinct tags; require the same here)
            errors.expects(
                len(send_keys) == len(sends),
                "p2p waitall: duplicate (src, dst, tag) sends %s — use "
                "distinct tags per in-flight transfer",
                sorted(k for k in sends if send_keys.count(k) > 1),
            )
            errors.expects(
                len(self._recvs) == len(recvs),
                "p2p waitall: duplicate (src, dst, tag) recvs %s",
                sorted(k for k in recvs if self._recvs.count(k) > 1),
            )
            errors.expects(
                sends == recvs,
                "p2p waitall: unmatched transfers (sends-only %s, "
                "recvs-only %s)",
                sorted(sends - recvs), sorted(recvs - sends),
            )
        except Exception:
            self._sends, self._recvs = [], []
            raise
        rank = self._comms.get_rank()
        out = {}
        by_tag = {}
        for s, d, t, v in self._sends:
            by_tag.setdefault(t, []).append((s, d, v))
        for t, entries in sorted(by_tag.items()):
            # greedy rounds: within a round every src and dst is unique
            remaining = list(entries)
            while remaining:
                # a round = unique sources, unique destinations, AND one
                # (shape, dtype) — a ppermute carries a single payload
                # type, so mixed-shape transfers split into further rounds
                round_entries, used_s, used_d, rest = [], set(), set(), []
                round_sig = None
                for s, d, v in remaining:
                    sig = (v.shape, v.dtype.name)
                    if (
                        s in used_s or d in used_d
                        or (round_sig is not None and sig != round_sig)
                    ):
                        rest.append((s, d, v))
                    else:
                        round_entries.append((s, d, v))
                        used_s.add(s)
                        used_d.add(d)
                        round_sig = sig
                remaining = rest
                # each rank contributes the value of ITS send in this round
                payload = sum(
                    jnp.where(rank == s, v, jnp.zeros_like(v))
                    for s, _, v in round_entries
                )
                perm = [(s, d) for s, d, _ in round_entries]
                delivered = self._comms.sendrecv(payload, perm)
                for s, d, _ in round_entries:
                    # per-transfer masking: a round's single ppermute result
                    # holds whatever THIS rank received; only the transfer
                    # whose destination is this rank may expose it — every
                    # other key reads zeros (the documented contract)
                    out[(s, d, t)] = jnp.where(
                        rank == d, delivered, jnp.zeros_like(delivered)
                    )
        self._sends, self._recvs = [], []
        return out


class Comms:
    """Host-side communicator bootstrap + injection — the analog of
    pyraft's ``Comms`` session (python/raft/raft/dask/common/comms.py:37-244)
    and of ``build_comms_nccl_only`` (comms/helper.hpp:37).

    Single-host: wraps the local devices in a mesh. Multi-host: call
    :meth:`initialize_distributed` first (replaces the Dask/NCCL-uniqueId
    rendezvous with jax.distributed).
    """

    def __init__(
        self,
        devices: Optional[Sequence] = None,
        axis: str = "ranks",
        mesh: Optional[jax.sharding.Mesh] = None,
    ):
        if mesh is not None:
            self.mesh = mesh
            self.axis = mesh.axis_names[0] if axis is None else axis
            # a tuple axis (HierarchicalComms collectives span both mesh
            # levels) is valid when every member names a mesh axis
            names = mesh.axis_names
            ok = (
                all(a in names for a in self.axis)
                if isinstance(self.axis, tuple)
                else self.axis in names
            )
            if not ok:
                self.axis = names[0]
        else:
            devs = list(devices) if devices is not None else jax.devices()
            self.mesh = jax.sharding.Mesh(np.array(devs), (axis,))
            self.axis = axis

    @staticmethod
    def initialize_distributed(
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
    ) -> None:
        """Multi-host bootstrap (replaces Dask + ncclCommInitRank rendezvous,
        reference comms.py:171-218 + nccl.pyx:52-57)."""
        jax.distributed.initialize(coordinator_address, num_processes, process_id)

    @property
    def size(self) -> int:
        return self.mesh.devices.size

    def device_comms(self) -> AxisComms:
        """The device-side facade to close over inside shard_map."""
        return AxisComms(self.axis)

    def replicate(self, x) -> jax.Array:
        """``x`` placed whole on every device of the mesh. The ``stage``
        of a :class:`~raft_tpu.serving.ServingExecutor` over a sharded
        index: its search program reads the queries replicated, so a
        batch staged here enters the program as it is, and a donated
        one is consumed in place, with no reshard in the dispatch."""
        return jax.device_put(
            x, jax.sharding.NamedSharding(self.mesh,
                                          jax.sharding.PartitionSpec()))

    def comm_split(self, colors: Sequence[int], keys: Optional[Sequence[int]] = None):
        """Partition ranks by color into sub-communicators
        (reference comms.hpp:189 / std_comms.hpp:144-180 ncclCommSplit-style).
        Returns {color: Comms} over the grouped devices, ordered by key."""
        devs = list(self.mesh.devices.flat)
        if keys is None:
            keys = list(range(len(devs)))
        groups: dict = {}
        for dev, color, key in sorted(
            zip(devs, colors, keys), key=lambda t: (t[1], t[2])
        ):
            groups.setdefault(color, []).append(dev)
        return {
            c: Comms(devices=g, axis=f"{self.axis}_split{c}")
            for c, g in groups.items()
        }

    def shard_map(self, fn, in_specs, out_specs):
        """Convenience: shard_map over this communicator's mesh.

        check_vma=False: comms-style code mixes replicated initial values
        with rank-varying collective results (scan carries, merge loops);
        the varying-manual-axes inference rejects those mixes even when
        semantically fine, exactly like a rank-symmetric NCCL program.

        Goes through :mod:`raft_tpu.compat` — ``shard_map``'s home and its
        check kwarg's name both moved across JAX releases.
        """
        return compat.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )


class HierarchicalComms(Comms):
    """Two-level communicator over an (outer, inner) device mesh — the
    multi-host topology: ``inner`` = chips within a slice (ICI), ``outer``
    = across hosts/slices (DCN). The reference reaches the same shape by
    nesting NCCL communicators via ``comm_split`` (std_comms.hpp:144-180);
    here both levels are axes of one ``jax.sharding.Mesh`` and XLA routes
    each collective over the matching interconnect.

    ``device_comms()`` (both axes at once), :meth:`inner_comms`, and
    :meth:`outer_comms` are all usable inside one ``shard_map`` over the
    2D mesh.
    """

    def __init__(self, devices=None, mesh_shape=None, axes=("dcn", "ici")):
        devs = np.array(list(devices) if devices is not None else jax.devices())
        if mesh_shape is None:
            mesh_shape = (1, devs.size)
        errors.expects(
            len(mesh_shape) == len(axes),
            "mesh_shape %s must have one dim per axis %s", mesh_shape, axes,
        )
        errors.expects(
            int(np.prod(mesh_shape)) == devs.size,
            "mesh_shape %s needs %d devices, got %d",
            mesh_shape, int(np.prod(mesh_shape)), devs.size,
        )
        self.mesh = jax.sharding.Mesh(devs.reshape(mesh_shape), axes)
        self.axes = tuple(axes)
        self.axis = self.axes  # collectives over BOTH levels by default

    @property
    def inner_size(self) -> int:
        """Chips per slice (the ICI width — one host's mesh)."""
        return int(self.mesh.shape[self.axes[1]])

    @property
    def outer_size(self) -> int:
        """Slices in the mesh (the DCN width — the host count)."""
        return int(self.mesh.shape[self.axes[0]])

    def host_of(self, rank: int) -> int:
        """The slice (host) holding flattened rank ``rank`` — ranks
        number row-major over (outer, inner), matching the slab layout
        of ``P((outer, inner), ...)`` sharded arrays."""
        errors.expects(
            0 <= rank < self.size,
            "rank %d out of range [0, %d)", rank, self.size,
        )
        return rank // self.inner_size

    def inner_comms(self) -> AxisComms:
        """Collectives within a slice (ICI-routed)."""
        return AxisComms(self.axes[1])

    def outer_comms(self) -> AxisComms:
        """Collectives across slices (DCN-routed)."""
        return AxisComms(self.axes[0])

    def device_comms(self) -> AxisComms:
        """Collectives over the flattened mesh (both axes): psum-family
        ops accept the axis tuple directly."""
        return AxisComms(self.axes)

    def hierarchical_allreduce(self, x):
        """Bandwidth-optimal multi-level allreduce, stated explicitly:
        reduce-scatter within the slice (ICI), allreduce the shards across
        slices (DCN moves only 1/inner_size of the bytes), allgather the
        result back within the slice — the structure NCCL's tree/hierarchy
        algorithms use across nodes. Call inside shard_map over the 2D
        mesh. A leading dim not divisible by the inner size is padded
        with zeros for the reduce-scatter and sliced back after the
        allgather (the old hard precondition turned every odd-shaped
        payload into a caller-side pad dance).
        """
        inner, outer = self.inner_comms(), self.outer_comms()
        inner_size = self.inner_size
        n0 = x.shape[0]
        rem = n0 % inner_size
        if rem:
            # zero rows are sum-neutral; they come back as garbage-free
            # zero rows and are sliced off below
            pad = [(0, inner_size - rem)] + [(0, 0)] * (x.ndim - 1)
            x = jnp.pad(x, pad)
        shard = inner.reducescatter(x, tiled=True)
        shard = outer.allreduce(shard)
        out = inner.allgather(shard, tiled=True)
        return out[:n0] if rem else out


def build_comms(devices=None, axis: str = "ranks") -> Comms:
    """Analog of ``build_comms_nccl_only`` (helper.hpp:37-45)."""
    return Comms(devices=devices, axis=axis)


def build_comms_hierarchical(
    devices=None, mesh_shape=None, axes=("dcn", "ici")
) -> HierarchicalComms:
    """Two-level (multi-host style) communicator; see
    :class:`HierarchicalComms`."""
    return HierarchicalComms(devices=devices, mesh_shape=mesh_shape, axes=axes)


def inject_comms(resources, comms: Comms) -> None:
    """Attach the communicator's mesh to a Resources handle — the analog of
    ``inject_comms_on_handle`` (python/raft/raft/dask/common/comms_utils.pyx:29-70
    → handle.set_comms, core/handle.hpp:239)."""
    resources.set_mesh(comms.mesh)
    resources.comms = comms
