"""Elastic GPT training with flash checkpointing — the flagship workflow.

Run on one host (spawns a local master automatically):

    tpurun --standalone --nnodes 1 examples/gpt_elastic.py

Or against a running master on a multi-host slice:

    DLROVER_MASTER_ADDR=<master:port> tpurun --nnodes 4 examples/gpt_elastic.py

Kill the worker (or the whole host) mid-run: the agent re-rendezvouses,
the script rebuilds the mesh from whatever world it lands in, and
``engine.load`` resumes from the shm-staged step — storage only if the
memory copy is gone. (Reference workflow: examples/pytorch/gpt elastic
jobs + flash_checkpoint.)
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.layers import cross_entropy_loss
from dlrover_tpu.parallel.mesh import build_mesh, choose_mesh_shape
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)
from dlrover_tpu.trainer.elastic import elastic_context

TOTAL_STEPS = int(os.environ.get("TOTAL_STEPS", "200"))
CKPT_DIR = os.environ.get("CKPT_DIR", "/tmp/gpt_elastic_ckpt")
# The model is chosen HERE, not by how many devices happen to be
# attached: "gpt2_small" (124M, the default) or "tiny" (a 2-layer toy
# for CPU walkthroughs). The device count decides the mesh only.
MODEL = os.environ.get("MODEL", "gpt2_small")
BATCH_PER_DEVICE = 2


def main():
    ctx = elastic_context()  # jax.distributed bootstrap from the agent env

    n = len(jax.devices())
    mesh = build_mesh(choose_mesh_shape(n, tp=1))
    cfg = {"gpt2_small": GPTConfig.gpt2_small, "tiny": GPTConfig.tiny}[MODEL]()
    model = GPT(cfg)
    tx = default_optimizer()
    batch = BATCH_PER_DEVICE * n

    tokens = jnp.zeros((batch, cfg.max_seq_len), jnp.int32)
    state, shardings = init_train_state(model, tokens, mesh, tx)
    step_fn = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)

    engine = CheckpointEngine(CKPT_DIR, mesh=mesh)
    # ElasticTrainLoop handles consistent resume (hosts agree on ONE
    # step after a replacement), the shm/storage save cadence, and step
    # reports feeding the master's PerfMonitor/goodput/hang machinery.
    from dlrover_tpu.trainer.loop import ElasticTrainLoop

    rng = np.random.default_rng(ctx.process_id)

    def data():
        # Host numpy on purpose: ElasticTrainLoop prefetches this
        # generator on a background thread (docs/recovery.md) — batch
        # prep belongs on the host there; the jitted step moves the
        # batch to the device on the main thread.
        while True:
            x = rng.integers(
                0, cfg.vocab_size, (batch, cfg.max_seq_len)
            ).astype(np.int32)
            yield x, np.roll(x, -1, axis=1)

    loop = ElasticTrainLoop(
        engine, step_fn, ctx=ctx, max_steps=TOTAL_STEPS, storage_every=50
    )
    loop.run(state, data())
    print("done")


if __name__ == "__main__":
    main()
