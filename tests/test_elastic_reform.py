"""Automatic re-formation after a node loss (VERDICT r03 task #8).

Two supervised nodes train data-parallel in a real two-process
``jax.distributed`` world.  Node 1 dies mid-training (trainer crashes and
its supervisor goes with it — a lost node, beacons stop).  Node 0's
trainer is killed by the coordination service's peer-death propagation;
its supervisor detects the abnormal exit, re-rendezvouses, finds only
itself alive, re-forms as a one-node generation-1 world, and relaunches
the trainer, which resumes from the last checkpoint and finishes.  The
loss sequence must continue falling across the generation boundary.

(Why recovery is supervisor-level, not in-process: jax 0.9.0 FATALs every
surviving task from the coordination service's error-polling thread — not
catchable from Python — and ``jax.distributed.initialize`` is
once-per-process.  See ``skycomputing_tpu/parallel/elastic.py``.)
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # two-process supervisor kill test

_TRAINER = textwrap.dedent(
    """
    import os, sys, time
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from skycomputing_tpu.parallel import global_mesh, initialize_from_env

    work = sys.argv[1]
    node_id = int(os.environ["ELASTIC_NODE_ID"])
    gen = int(os.environ["SKYTPU_GENERATION"])
    rank = int(os.environ["SKYTPU_PROCESS_ID"])
    assert initialize_from_env() is True

    TOTAL_ITERS = 8
    rng = np.random.default_rng(0)
    X = rng.normal(size=(8, 16)).astype(np.float32)
    y = rng.normal(size=(8, 4)).astype(np.float32)

    mesh = global_mesh(("dp",), (len(jax.devices()),))
    xs = jax.make_array_from_callback(
        X.shape, NamedSharding(mesh, P("dp")), lambda idx: X[idx]
    )
    ys = jax.make_array_from_callback(
        y.shape, NamedSharding(mesh, P("dp")), lambda idx: y[idx]
    )

    ckpt = os.path.join(work, "ckpt.npz")
    if os.path.exists(ckpt):
        blob = np.load(ckpt)
        W0, start = blob["W"], int(blob["it"])
    else:
        W0, start = np.zeros((16, 4), np.float32), 0

    @jax.jit
    def step(W, xb, yb):
        def loss_fn(W):
            return jnp.mean((xb @ W - yb) ** 2)
        l, g = jax.value_and_grad(loss_fn)(W)
        return W - 0.02 * g, l

    W = jax.device_put(jnp.asarray(W0), NamedSharding(mesh, P()))
    for it in range(start, TOTAL_ITERS):
        W, l = step(W, xs, ys)
        l = float(jax.block_until_ready(l))
        if rank == 0:
            with open(os.path.join(work, "losses.log"), "a") as fh:
                fh.write(f"{gen} {it} {l:.8f}\\n")
            tmp = os.path.join(work, "ckpt_tmp")
            np.savez(tmp, W=np.asarray(W), it=it + 1)
            os.replace(tmp + ".npz", ckpt)
        # node 1 is "lost" here: trainer dies, supervisor follows
        if node_id == 1 and gen == 0 and it == 2:
            os._exit(3)
    print(f"TRAINER_DONE node={node_id} gen={gen}", flush=True)
    """
)

_SUPERVISOR = textwrap.dedent(
    """
    import json, os, sys
    from skycomputing_tpu.parallel.elastic import ElasticSupervisor

    node_id = int(sys.argv[1]); rdv = sys.argv[2]
    trainer = sys.argv[3]; work = sys.argv[4]
    max_reforms = int(sys.argv[5])

    env = dict(os.environ)
    env["ELASTIC_NODE_ID"] = str(node_id)

    sup = ElasticSupervisor(
        node_id, rdv,
        trainer_cmd=lambda spec, rank: [sys.executable, trainer, work],
        expect=2, max_reforms=max_reforms, env=env,
        stale_s=6.0, settle_s=2.0, timeout_s=90.0,
    )
    rc = sup.run()
    print("GENERATIONS " + json.dumps(
        [s["members"] for s in sup.generations]), flush=True)
    sys.exit(0 if rc == 0 else 1)
    """
)


def test_node_loss_reforms_and_resumes(tmp_path):
    work = tmp_path / "work"
    rdv = tmp_path / "rdv"
    work.mkdir()
    trainer = tmp_path / "trainer.py"
    supervisor = tmp_path / "supervisor.py"
    trainer.write_text(_TRAINER)
    supervisor.write_text(_SUPERVISOR)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    procs = []
    for node_id, max_reforms in ((0, 3), (1, 0)):
        procs.append(
            subprocess.Popen(
                [sys.executable, str(supervisor), str(node_id), str(rdv),
                 str(trainer), str(work), str(max_reforms)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append((p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    rc0, out0 = outs[0]
    rc1, out1 = outs[1]
    assert rc0 == 0, f"survivor supervisor failed rc={rc0}\n{out0[-3000:]}"
    assert rc1 != 0, "lost node's supervisor must report failure"

    # the survivor went through exactly two generations: [0,1] then [0]
    gens = json.loads(out0.split("GENERATIONS ", 1)[1].splitlines()[0])
    assert gens[0] == [0, 1] and gens[-1] == [0], gens

    # loss log: continuous iters across the generation boundary, falling
    rows = [ln.split() for ln in
            (work / "losses.log").read_text().splitlines()]
    by_iter = {int(it): (int(g), float(l)) for g, it, l in rows}
    assert sorted(by_iter) == list(range(8)), sorted(by_iter)
    gens_seen = {g for g, _ in by_iter.values()}
    assert gens_seen == {0, 1}, gens_seen
    losses = [by_iter[i][1] for i in range(8)]
    assert losses[-1] < losses[3] < losses[0], losses
    # the resumed trajectory must CONTINUE, not restart: every post-reform
    # loss is below the last pre-crash loss
    crash_gen_losses = [l for i, (g, l) in by_iter.items() if g == 0]
    resumed = [l for i, (g, l) in by_iter.items() if g == 1]
    assert min(resumed) < min(crash_gen_losses)
    assert max(resumed) < min(crash_gen_losses)


# --------------------------------------------------------- planned re-form
_REALLOC_TRAINER = textwrap.dedent(
    """
    import json, os, sys
    import numpy as np

    from skycomputing_tpu.parallel.elastic import REALLOC_RC, FileRendezvous

    work = sys.argv[1]
    gen = int(os.environ["SKYTPU_GENERATION"])
    rank = int(os.environ["SKYTPU_PROCESS_ID"])
    rdv_dir = os.environ["SKYTPU_RENDEZVOUS"]  # exported by the supervisor

    TOTAL_ITERS = 8
    ckpt = os.path.join(work, "ckpt.npz")
    if os.path.exists(ckpt):
        blob = np.load(ckpt)
        W, start = blob["W"], int(blob["it"])
    else:
        W, start = np.zeros((4,), np.float32), 0

    if gen >= 1:
        # the re-formed world must carry the staged measurement
        alloc = json.loads(os.environ["SKYTPU_ALLOCATION"])
        with open(os.path.join(work, "carried_allocation.json"), "w") as fh:
            json.dump(dict(alloc, resumed_at=start, gen=gen), fh)

    for it in range(start, TOTAL_ITERS):
        W = W + 1.0  # a 'step' whose effect the resume must not repeat
        with open(os.path.join(work, "iters.log"), "a") as fh:
            fh.write(f"{gen} {it} {float(W[0]):.1f}\\n")
        tmp = os.path.join(work, "ckpt_tmp")
        np.savez(tmp, W=W, it=it + 1)
        os.replace(tmp + ".npz", ckpt)
        if gen == 0 and it == 3:
            # self-heal exit: snapshot is on disk, stage the measured
            # device scales, ask the supervisor for a planned re-form
            FileRendezvous(rdv_dir, rank).stage_payload(
                {"device_scale": {"2": 3.0}, "iter": it}
            )
            sys.exit(REALLOC_RC)
    print(f"TRAINER_DONE gen={gen}", flush=True)
    """
)

_REALLOC_SUPERVISOR = textwrap.dedent(
    """
    import json, os, sys
    from skycomputing_tpu.parallel.elastic import ElasticSupervisor

    node_id = int(sys.argv[1]); rdv = sys.argv[2]
    trainer = sys.argv[3]; work = sys.argv[4]

    sup = ElasticSupervisor(
        node_id, rdv,
        trainer_cmd=lambda spec, rank: [sys.executable, trainer, work],
        expect=1,
        max_reforms=0,   # NO crash budget: a planned re-form must not spend it
        max_reallocs=2,
        stale_s=6.0, settle_s=0.5, timeout_s=60.0,
    )
    rc = sup.run()
    print("GENERATIONS " + json.dumps(
        [s["members"] for s in sup.generations]), flush=True)
    sys.exit(rc)
    """
)


def test_realloc_rc_reforms_and_resumes_at_saved_iter(tmp_path):
    """A trainer exiting REALLOC_RC (the SelfHealHook's planned re-form)
    is relaunched in a new generation that resumes at the saved iter and
    sees the staged allocation through world.json — and with
    ``max_reforms=0`` the planned exit provably does not spend the
    crash-recovery budget."""
    import json as json_mod

    work = tmp_path / "work"
    rdv = tmp_path / "rdv"
    work.mkdir()
    trainer = tmp_path / "trainer.py"
    supervisor = tmp_path / "supervisor.py"
    trainer.write_text(_REALLOC_TRAINER)
    supervisor.write_text(_REALLOC_SUPERVISOR)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    proc = subprocess.Popen(
        [sys.executable, str(supervisor), "0", str(rdv), str(trainer),
         str(work)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-3000:]

    # two generations, same single-node membership
    gens = json_mod.loads(out.split("GENERATIONS ", 1)[1].splitlines()[0])
    assert gens == [[0], [0]], gens

    # iteration log: continuous across the planned re-form, no replay
    rows = [ln.split() for ln in (work / "iters.log").read_text().splitlines()]
    assert [(int(g), int(it)) for g, it, _ in rows] == (
        [(0, i) for i in range(4)] + [(1, i) for i in range(4, 8)]
    )
    # W incremented exactly once per iter across the boundary
    assert [float(w) for _, _, w in rows] == [float(i + 1) for i in range(8)]

    # the staged measurement rode through world.json into the relaunch
    carried = json_mod.loads(
        (work / "carried_allocation.json").read_text()
    )
    assert carried["device_scale"]["2"] == 3.0
    assert carried["resumed_at"] == 4 and carried["gen"] == 1
