"""Multi-slice elasticity: slice-aware mesh, scaling, and rendezvous.

SURVEY §7 hard-parts: the realistic elastic unit on TPU is a SLICE —
dp rides DCN between slices, every other axis' collectives must stay
on a slice's ICI, and the master grows/shrinks/recovers in whole-slice
steps (reference node_unit semantics, rdzv_manager.py:179-181).
8 virtual CPU devices (conftest) model 2 slices of 4 chips.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.parallel.mesh import (
    MeshConfig,
    SliceTopology,
    build_mesh,
    build_multislice_mesh,
    choose_multislice_shape,
)


def _meta(rank, slice_id=0):
    return comm.NodeMeta(
        node_id=rank, node_rank=rank, process_unit=1,
        addr=f"10.0.{slice_id}.{rank}", slice_id=slice_id,
    )


class TestMultisliceMesh:
    def test_choose_shape_dp_across_fsdp_within(self):
        cfg = choose_multislice_shape(SliceTopology(2, 4), tp=2)
        assert cfg.dp == 2  # one data shard per slice — DCN carries dp only
        assert cfg.fsdp == 2 and cfg.tp == 2  # ICI-bound, intra-slice

    def test_choose_shape_rejects_ici_axes_larger_than_slice(self):
        with pytest.raises(ValueError, match="cross DCN"):
            choose_multislice_shape(SliceTopology(2, 4), tp=8)

    def test_build_validates_inner_axes_stay_on_ici(self):
        devices = jax.devices()[:8]
        topo = SliceTopology(2, 4)
        mesh = build_multislice_mesh(
            MeshConfig(dp=2, fsdp=2, tp=2), topo, devices
        )
        # identical device layout to the plain builder — the multislice
        # call adds the DCN-boundary validation, not a new layout
        plain = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices)
        assert (mesh.devices == plain.devices).all()
        # fsdp*tp = 8 > slice_size: an fsdp shard would span slices
        with pytest.raises(ValueError, match="DCN boundary"):
            build_multislice_mesh(
                MeshConfig(dp=1, fsdp=4, tp=2), topo, devices
            )
        with pytest.raises(ValueError, match="devices"):
            build_multislice_mesh(
                MeshConfig(dp=2, fsdp=2), SliceTopology(2, 2), devices
            )

    def test_slice_loss_remesh_trains(self):
        """Losing a whole slice re-meshes as a pure dp shrink: the
        per-slice layout is unchanged and the survivor world trains."""
        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.models.layers import cross_entropy_loss
        from dlrover_tpu.parallel.train_step import (
            build_train_step,
            default_optimizer,
            init_train_state,
        )

        cfg = GPTConfig(
            vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2,
            head_dim=8, embed_dim=16, use_remat=False,
        )
        model, tx = GPT(cfg), default_optimizer()
        r = np.random.default_rng(0)

        def one_step(topo, devices):
            mesh = build_multislice_mesh(
                choose_multislice_shape(topo, tp=2), topo, devices
            )
            batch = 2 * mesh.shape["dp"] * mesh.shape["fsdp"]
            state, sh = init_train_state(
                model, jnp.zeros((batch, 32), jnp.int32), mesh, tx
            )
            step = build_train_step(model, tx, cross_entropy_loss, mesh, sh)
            x = jnp.asarray(
                r.integers(0, cfg.vocab_size, (batch, 32)), jnp.int32
            )
            _, loss = step(state, x, jnp.roll(x, -1, axis=1))
            return float(loss)

        devices = jax.devices()[:8]
        assert np.isfinite(one_step(SliceTopology(2, 4), devices))
        # slice 1 dies — survivors are slice 0's 4 devices
        assert np.isfinite(one_step(SliceTopology(1, 4), devices[:4]))


class TestSliceAwareScaling:
    @pytest.fixture(autouse=True)
    def fresh_ctx(self):
        from dlrover_tpu.master.job_context import JobContext

        JobContext.reset()
        yield
        JobContext.reset()

    def _manager(self, slice_ids):
        from dlrover_tpu.master.job_context import get_job_context
        from dlrover_tpu.master.node.dist_job_manager import (
            DistributedJobManager,
        )
        from tests.test_dist_master import RecordingScaler, _worker

        from dlrover_tpu.common.constants import NodeStatus, NodeType

        scaler = RecordingScaler()
        m = DistributedJobManager(num_workers=len(slice_ids), scaler=scaler)
        m.start()
        ctx = get_job_context()
        for nid, sid in enumerate(slice_ids):
            node = ctx.get_node(NodeType.WORKER, nid)
            node.update_status(NodeStatus.RUNNING)
            node.slice_id = sid
            ctx.update_node(node)
        return m, scaler

    def test_scale_down_truncates_to_slice_boundary(self):
        """A shrink target cutting through a slice releases the WHOLE
        top slice instead: a slice missing hosts can't form its mesh."""
        m, scaler = self._manager([0, 0, 1, 1])
        try:
            removed = m.scale_down(3)  # mid-slice-1 target → boundary 2
            assert removed == [2, 3]
            assert m.num_workers == 2
        finally:
            m.stop()

    def test_scale_down_below_first_boundary_keeps_one_slice(self):
        """A nonzero target below one slice rounds UP: a shrink request
        must never silently kill the whole job."""
        m, _ = self._manager([0, 0, 1, 1])
        try:
            assert m.scale_down(1) == [2, 3]
            assert m.num_workers == 2
        finally:
            m.stop()

    def test_scale_down_aligned_target_untouched(self):
        m, _ = self._manager([0, 0, 1, 1])
        try:
            assert m.scale_down(2) == [2, 3]
        finally:
            m.stop()

    def test_single_slice_world_shrinks_node_granular(self):
        """One slice (or no slice info) keeps the reference's
        node-granular behavior — nothing to align against."""
        m, _ = self._manager([3, 3, 3, 3])
        try:
            assert m.scale_down(3) == [3]
        finally:
            m.stop()


class TestSliceRendezvous:
    def test_whole_slice_loss_reforms_surviving_slice(self):
        """2 slices × 2 hosts; slice 1 dies; the next wave completes
        with slice 0 alone — truncation to node_unit already guarantees
        slice granularity, topology sort keeps the survivors dense."""
        from dlrover_tpu.master.rdzv.manager import (
            ElasticTrainingRendezvousManager,
        )

        m = ElasticTrainingRendezvousManager()
        m.update_rdzv_params(
            min_nodes=2, max_nodes=4, waiting_timeout=60, node_unit=2
        )
        for rank, sid in ((0, 0), (1, 0), (2, 1), (3, 1)):
            m.join_rendezvous(_meta(rank, slice_id=sid))
        _, _, world = m.get_comm_world(0)
        assert len(world) == 4

        # slice 1's hosts die; survivors re-join the next wave
        m.remove_alive_node(2)
        m.remove_alive_node(3)
        m._lastcall_timeout = 0.1
        m.join_rendezvous(_meta(0, slice_id=0))
        m.join_rendezvous(_meta(1, slice_id=0))
        time.sleep(0.2)
        _, _, world = m.get_comm_world(0)
        assert len(world) == 2
        assert all(meta.slice_id == 0 for meta in world.values())
