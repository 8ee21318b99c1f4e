"""Worker process for the multi-process DCN-bootstrap and elastic tests.

Usage:
    python _dist_worker.py <coordinator> <num_processes> <process_id>
        [--local-dp]
    python _dist_worker.py --elastic <shared_dir> <process_id> <world>
        [sigkill_at_step]

``--elastic`` runs the supervised elastic runtime (parallel/elastic.py):
membership over a shared directory (NOT jax.distributed — a SIGKILLed peer
must not take the PJRT control plane down with it; the data plane per
process is local DP, the r7 CPU-backend stance), checkpoint-auto-resume,
epoch-boundary regroup. With ``sigkill_at_step`` the process arms the
``sigkill_host`` fault against itself — the surviving process must notice
the missed heartbeats, regroup to a smaller world, re-shard the batches,
and finish."""

import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from deeplearning4j_tpu.parallel import distributed  # noqa: E402


def _report(fields):
    """The one line the parent test reads: the result, and the XLA_FLAGS
    this process compiled under (the test checks that the suite's
    optimisation level reached its children)."""
    print(json.dumps(dict(fields, xla_flags=os.environ.get("XLA_FLAGS", ""))),
          flush=True)


def _local_dp(nproc, pid):
    """``--local-dp`` mode (the __graft_entry__ DCN dryrun): prove the
    CONTROL plane — gRPC coordinator bootstrap, global device view, process
    roles — then run the DP step on this process's own addressable devices.
    The cross-process data plane is probed but allowed to be unavailable:
    this jaxlib's CPU backend rejects multiprocess computations ("Multiprocess
    computations aren't implemented on the CPU backend"), a backend ceiling,
    not a bootstrap defect — on TPU the same program spans the global mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_global, n_local = len(jax.devices()), len(jax.local_devices())
    mesh = Mesh(np.array(jax.local_devices()), ("data",))

    D = 8
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(D,)).astype(np.float32)
    B = 4 * n_local
    X = rng.normal(size=(B, D)).astype(np.float32)
    Y = X @ w_true
    x = jax.device_put(X, NamedSharding(mesh, P("data")))
    y = jax.device_put(Y, NamedSharding(mesh, P("data")))
    w = jax.device_put(np.zeros((D,), np.float32), NamedSharding(mesh, P()))

    @jax.jit
    def step(w, x, y):
        g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
        return w - 0.2 * g

    for _ in range(30):
        w = step(w, x, y)
    err = float(np.abs(np.asarray(jax.device_get(w)) - w_true).max())

    # opportunistic global-step probe: works on real multi-host backends,
    # expected to be rejected by the CPU backend
    try:
        gmesh = distributed.global_mesh().mesh
        xg = jax.make_array_from_process_local_data(
            NamedSharding(gmesh, P("data")), X[: B // nproc])
        jax.jit(lambda a: a * 2.0)(xg).block_until_ready()
        global_step = "ok"
    except Exception as e:  # noqa: BLE001
        global_step = f"unavailable ({type(e).__name__})"

    _report({
        "pid": pid,
        "coordinator": distributed.is_coordinator(),
        "n_devices_global": n_global,
        "n_devices_local": n_local,
        "local_dp_err": round(err, 6),
        "global_step": global_step,
    })
    distributed.shutdown()


def _elastic(shared_dir, pid, world, sigkill_at=None):
    """``--elastic`` mode: one member of a supervised elastic pod."""
    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel import ElasticTrainer, FileMembership
    from deeplearning4j_tpu.util.faults import SIGKILL_HOST, get_injector

    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.05))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=4, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)  # same data recipe on every member
    xs = rng.standard_normal((64, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
    it = ArrayDataSetIterator(xs, ys, batch=8)  # 8 batches / epoch

    if sigkill_at is not None:
        get_injector().inject(SIGKILL_HOST, at_step=sigkill_at)
    membership = FileMembership(
        os.path.join(shared_dir, "membership"), process_id=pid,
        world_size=world, heartbeat_interval=0.3, miss_threshold=8,
        barrier_timeout=90.0, log_fn=None)
    trainer = ElasticTrainer(
        net, os.path.join(shared_dir, f"ckpt-{pid}"), checkpoint_every=4,
        membership=membership, log_fn=None)
    trainer.fit(it, epochs=3)
    view = membership.view
    _report({
        "pid": pid,
        "state": trainer.state,
        "iteration": net.iteration,
        "epoch": net.epoch,
        "world_final": view.world if view else None,
        "members_final": list(view.members) if view else None,
        "regroups": membership.regroups,
        "score_finite": bool(np.isfinite(float(net.score_value))),
    })


def _elastic_compress(shared_dir, pid, world, sigkill_at=None):
    """``--elastic-compress`` mode: one member of a supervised elastic pod
    whose data plane is the COMPRESSED ParallelWrapper DP step
    (parallel/compression.py). Proves the residual/threshold state rides
    the elastic machinery: a SIGKILLed peer's loss regroups the survivor
    (whose wrapper re-shards with its residual migrated in place), and the
    final checkpoint carries the residual EXACTLY (bit-compared against a
    fresh restore before reporting)."""
    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel import (ElasticTrainer, FileMembership,
                                             ParallelWrapper, TrainingMesh)
    from deeplearning4j_tpu.util.faults import SIGKILL_HOST, get_injector

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.05))
                .grad_compression("threshold", threshold=1e-3)
                .list()
                .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
                .layer(OutputLayer(n_in=16, n_out=4, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(8)).build())
        return MultiLayerNetwork(conf).init()

    net = build_net()
    pw = ParallelWrapper(net, mesh=TrainingMesh(data=len(jax.devices())),
                         replicas=4, skew_every=0)
    rng = np.random.default_rng(0)  # same data recipe on every member
    xs = rng.standard_normal((64, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
    it = ArrayDataSetIterator(xs, ys, batch=8)  # 8 batches / epoch

    if sigkill_at is not None:
        get_injector().inject(SIGKILL_HOST, at_step=sigkill_at)
    membership = FileMembership(
        os.path.join(shared_dir, "membership"), process_id=pid,
        world_size=world, heartbeat_interval=0.3, miss_threshold=8,
        barrier_timeout=90.0, log_fn=None)
    trainer = ElasticTrainer(
        pw, os.path.join(shared_dir, f"ckpt-{pid}"), checkpoint_every=4,
        membership=membership, log_fn=None)
    trainer.fit(it, epochs=3)

    # checkpoint-resume carries the residual exactly: restore the FINAL
    # checkpoint into a fresh net and bit-compare the compression state
    net2 = build_net()
    trainer.ckpt.restore(net2)
    live = jax.tree_util.tree_leaves(net._grad_comp_state)
    restored = jax.tree_util.tree_leaves(net2._grad_comp_state)
    residual_exact = (
        len(live) == len(restored)
        and all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(live, restored))
        and any(np.asarray(a).any() for a in live))  # non-trivial residual

    view = membership.view
    stats = pw.compression_stats()
    _report({
        "pid": pid,
        "state": trainer.state,
        "iteration": net.iteration,
        "epoch": net.epoch,
        "world_final": view.world if view else None,
        "members_final": list(view.members) if view else None,
        "regroups": membership.regroups,
        "score_finite": bool(np.isfinite(float(net.score_value))),
        "residual_exact": bool(residual_exact),
        "wire_bytes": stats["wire_bytes"] if stats else None,
        "threshold": stats["threshold"] if stats else None,
    })


def _pipe(shared_dir, pid, world, sigkill_at=None):
    """``--pipe`` mode: one member of a supervised elastic pod whose data
    plane is the PIPELINED trainer (parallel/pipelined.py) — stacked stage
    params/optimizer state, GPipe microbatch schedule, lane-decomposed DP.
    Proves the stacked stage state rides the elastic machinery: a
    SIGKILLed peer's loss regroups the survivor (reshard() syncs the
    stacked state through model layout and re-places it), and the final
    checkpoint restores BIT-exactly at the boundary (the restored net's
    re-stacked pipeline state is bit-compared in-process against the live
    trainer's)."""
    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel import (ElasticTrainer, FileMembership,
                                             PipelinedTrainer, TrainingMesh)
    from deeplearning4j_tpu.util.faults import SIGKILL_HOST, get_injector

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
                .pipe_stages(2).n_micro(2)
                .list()
                .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
                .stage_boundary()
                .layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
                .stage_boundary()
                .layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
                .stage_boundary()
                .layer(OutputLayer(n_in=16, n_out=4, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(8)).build())
        return MultiLayerNetwork(conf).init()

    def build_trainer(net):
        return PipelinedTrainer(
            net, mesh=TrainingMesh(data=len(jax.devices())),
            replicas=2, skew_every=0)

    net = build_net()
    pt = build_trainer(net)
    rng = np.random.default_rng(0)  # same data recipe on every member
    xs = rng.standard_normal((64, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
    it = ArrayDataSetIterator(xs, ys, batch=8)  # 8 batches / epoch

    if sigkill_at is not None:
        get_injector().inject(SIGKILL_HOST, at_step=sigkill_at)
    membership = FileMembership(
        os.path.join(shared_dir, "membership"), process_id=pid,
        world_size=world, heartbeat_interval=0.3, miss_threshold=8,
        barrier_timeout=90.0, log_fn=None)
    trainer = ElasticTrainer(
        pt, os.path.join(shared_dir, f"ckpt-{pid}"), checkpoint_every=4,
        membership=membership, log_fn=None)
    trainer.fit(it, epochs=3)

    # the final (blocking, synced) checkpoint must restore the STACKED
    # stage state bit-exactly: restore into a fresh net, re-stack through
    # a fresh trainer, and compare every placed leaf
    net2 = build_net()
    trainer.ckpt.restore(net2)
    pt2 = build_trainer(net2)
    pt2._build()
    pt.sync_model()  # no-op value-wise (fit already synced at checkpoint)
    live = jax.tree_util.tree_leaves(
        {"params": pt._pp["params"], "opts": pt._pp["opts"]})
    restored = jax.tree_util.tree_leaves(
        {"params": pt2._pp["params"], "opts": pt2._pp["opts"]})
    stacked_exact = (
        len(live) == len(restored)
        and all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(live, restored)))

    view = membership.view
    _report({
        "pid": pid,
        "state": trainer.state,
        "iteration": net.iteration,
        "epoch": net.epoch,
        "world_final": view.world if view else None,
        "members_final": list(view.members) if view else None,
        "regroups": membership.regroups,
        "score_finite": bool(np.isfinite(float(net.score_value))),
        "stacked_exact": bool(stacked_exact),
        "pipe_stages": pt.pipe_stages,
        "bubble_fraction": pt.bubble_fraction,
    })


def main():
    if sys.argv[1] == "--pipe":
        _pipe(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
              int(sys.argv[5]) if len(sys.argv) > 5 else None)
        return
    if sys.argv[1] == "--elastic":
        _elastic(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
                 int(sys.argv[5]) if len(sys.argv) > 5 else None)
        return
    if sys.argv[1] == "--elastic-compress":
        _elastic_compress(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
                          int(sys.argv[5]) if len(sys.argv) > 5 else None)
        return
    coordinator, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    distributed.initialize(coordinator=coordinator, num_processes=nproc,
                           process_id=pid)
    assert distributed.process_count() == nproc
    assert distributed.process_index() == pid
    assert distributed.is_coordinator() == (pid == 0)

    if len(sys.argv) > 4 and sys.argv[4] == "--local-dp":
        _local_dp(nproc, pid)
        return

    tmesh = distributed.global_mesh()
    mesh = tmesh.mesh
    n_dev = len(jax.devices())

    D = 8
    rng = np.random.default_rng(0)  # same data recipe on every process
    w_true = rng.normal(size=(D,)).astype(np.float32)
    # deterministic global batch; each process materializes its local rows
    B = 4 * n_dev
    X = rng.normal(size=(B, D)).astype(np.float32)
    Y = X @ w_true

    xsh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    n_local = B // nproc
    lo = pid * n_local

    @jax.jit
    def step(w, x, y):
        def loss(w):
            return jnp.mean((x @ w - y) ** 2)

        g = jax.grad(loss)(w)  # partitioner inserts the cross-host allreduce
        return w - 0.2 * g

    data_plane = "global"
    try:
        x = jax.make_array_from_process_local_data(xsh, X[lo: lo + n_local])
        y = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data")), Y[lo: lo + n_local])
        w = jax.make_array_from_process_local_data(
            rep, np.zeros((D,), np.float32))
        for _ in range(30):
            w = step(w, x, y)
        w_final = np.asarray(jax.device_get(w))
    except Exception as e:  # noqa: BLE001
        # This jaxlib's CPU backend rejects cross-process computations
        # ("Multiprocess computations aren't implemented on the CPU
        # backend") — a backend ceiling, not a bootstrap defect (the r7
        # DCN-dryrun stance). Fall back LOUDLY: every process runs the
        # SAME deterministic global-batch DP step on its local 2-device
        # mesh, so the cross-process identity assertion still has teeth
        # (identical programs on identical data must agree bit-for-bit)
        # while the global device view proves the control plane. On real
        # ICI/DCN hardware the try-branch is the path that runs.
        if "Multiprocess computations" not in repr(e):
            raise
        data_plane = f"local_fallback({type(e).__name__}: cpu backend)"
        from jax.sharding import Mesh

        lmesh = Mesh(np.array(jax.local_devices()), ("data",))
        lsh = NamedSharding(lmesh, P("data"))
        lrep = NamedSharding(lmesh, P())
        x = jax.device_put(X, lsh)
        y = jax.device_put(Y, lsh)
        w = jax.device_put(np.zeros((D,), np.float32), lrep)
        for _ in range(30):
            w = step(w, x, y)
        w_final = np.asarray(jax.device_get(w))
    _report({
        "pid": pid,
        "n_devices_global": n_dev,
        "data_plane": data_plane,
        "w": [round(float(v), 6) for v in w_final],
        "err": round(float(np.abs(w_final - w_true).max()), 6),
    })
    distributed.shutdown()


if __name__ == "__main__":
    main()
