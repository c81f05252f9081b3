"""Child process for the 2-process train -> preempt -> resume test.

Run by tests/test_multihost.py as:
    python tests/_multihost_train_child.py <port> <process_id> <nproc> <dir>

Each process owns 2 virtual CPU devices (4 global).  The child runs the
REAL ``train()`` loop three times against synthetic data:

  A. straight:  6 steps start-to-finish                 -> params_A
  B. preempted: the batch stream raises SystemExit(143) after step 3 on
     both hosts at the same boundary (the agreed-step exit shape; the
     per-host _PREEMPT flag is single-host-only) — mid-epoch, past the
     step-2 periodic checkpoint; the loop's emergency save must flush
     step 3;
  C. resumed:   same checkpoint dir, runs 3 -> 6        -> params_C

and asserts ``params_A == params_C`` bit-level.  Equality proves ALL
continuity at once: step counter, optimizer/OneCycle-LR state and the
loader's mid-epoch shuffle position survive the kill (the pod preemption
path the reference loses — its torch.save is weights-only,
reference train.py:141-142,185-187).
"""

import os
import sys

port, pid, nproc, workdir = (sys.argv[1], int(sys.argv[2]),
                             int(sys.argv[3]), sys.argv[4])

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Multi-process CPU runs need an explicit collectives backend (see
# tests/_multihost_child.py).
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=nproc, process_id=pid)

import numpy as np  # noqa: E402

from raft_tpu.config import RAFTConfig, TrainConfig  # noqa: E402
from raft_tpu.data.datasets import ShardedLoader  # noqa: E402
from raft_tpu.train.loop import train  # noqa: E402

from loader_datasets import RandomPairs  # noqa: E402

H, W = 48, 64
NUM_STEPS, PREEMPT_AT, VAL_FREQ = 6, 3, 2


class PreemptingLoader:
    """Delegates to a real ShardedLoader but raises ``SystemExit(143)``
    after ``stop_after`` batches — on EVERY host at the SAME batch
    boundary, standing in for the coordination-service agreed-step exit
    (``reached_preemption_sync_point``).  The per-host ``_PREEMPT`` flag
    is deliberately NOT used here: it is single-host-only by design
    (``train()`` gates it on ``process_count() == 1`` so one host's flag
    can never strand the others in a collective)."""

    def __init__(self, loader, stop_after):
        self._loader = loader
        self._stop_after = stop_after

    def batches_from_step(self, step):
        inner = self._loader.batches_from_step(step)

        def gen():
            for n, batch in enumerate(inner):
                if n == self._stop_after:
                    raise SystemExit(143)  # agreed step on all hosts
                yield batch

        return gen()


def make_loader():
    return ShardedLoader(RandomPairs(16, (H, W)), batch_size=2, seed=7,
                         num_hosts=nproc, host_id=pid, num_workers=2)


# Tiny pyramid: what this test pins (distributed batch assembly,
# agreed-step preemption, checkpoint continuity) is independent of the
# correlation shape, and the full small-model graph dominates the
# 2-process XLA-CPU compile time on the 1-core container.
model_cfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
B_global = 2 * nproc


def cfg_for(name):
    return TrainConfig(name=name, num_steps=NUM_STEPS, batch_size=B_global,
                       image_size=(H, W), iters=2, val_freq=VAL_FREQ,
                       ckpt_dir=os.path.join(workdir, "ckpts"), seed=7,
                       log_freq=2)


# A: straight 6-step run.
state_a = train(model_cfg, cfg_for("straight"), loader=make_loader())
assert int(state_a.step) == NUM_STEPS, int(state_a.step)

# B: preempted at step 3 (after the step-2 periodic save — the emergency
# save must write step 3 or resume replays a stale shuffle position).
try:
    train(model_cfg, cfg_for("resume"),
          loader=PreemptingLoader(make_loader(), PREEMPT_AT))
    raise AssertionError("preemption did not propagate")
except SystemExit as e:
    assert e.code == 143, e.code

# C: resume in a fresh loop instance; must continue 3 -> 6.
state_c = train(model_cfg, cfg_for("resume"), loader=make_loader())
assert int(state_c.step) == NUM_STEPS, int(state_c.step)

mismatches = []
for (path_a, leaf_a), (_, leaf_c) in zip(
        jax.tree_util.tree_leaves_with_path(state_a.params),
        jax.tree_util.tree_leaves_with_path(state_c.params)):
    if not np.array_equal(np.asarray(leaf_a), np.asarray(leaf_c)):
        mismatches.append(jax.tree_util.keystr(path_a))
assert not mismatches, f"split-run params diverge: {mismatches[:5]}"

print(f"proc {pid}: preempt/resume == straight run OK", flush=True)
jax.distributed.shutdown()
