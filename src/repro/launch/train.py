DOC = """Federated training driver — the end-to-end e2e deliverable.

Two modes:
  sim  — full FL-APU control plane: governance negotiation -> contract ->
         job -> pull-based rounds over the message board -> deployment.
         (in-process consortium; the paper's architecture end to end)
  pod  — the TPU data plane: silo-per-pod training with vmap(spmd_axis) over
         a (pod, data, model) host mesh, K local steps between FedAvg
         collectives (DiLoCo-style local SGD; DESIGN.md §2). Runs on CPU
         host devices here, unchanged on a real multi-pod mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode sim --arch fedforecast-100m \
      --rounds 3 --local-steps 5 --batch-size 4
  PYTHONPATH=src python -m repro.launch.train --mode pod --arch fedforecast-100m \
      --steps 8 --sync-every 4
"""

import argparse
import os
import time

import numpy as np


def run_sim(args):
    import jax
    from repro.core import Consortium, DataSchema
    from repro.core.reporting import run_report
    from repro.data import make_silo_datasets

    orgs = [f"org{i}" for i in range(args.silos)]
    con = Consortium(orgs, seed=args.seed)
    from repro.configs import get_config
    cfg = get_config(args.arch)
    cfg_r = cfg.reduced() if args.reduced else cfg
    schema = DataSchema(vocab=cfg_r.vocab, seq_len=args.seq_len)
    contract = con.negotiate({
        "arch": args.arch, "rounds": args.rounds,
        "local_steps": args.local_steps, "batch_size": args.batch_size,
        "lr": args.lr, "data_schema": schema.to_dict(),
        "secure_aggregation": not args.no_secure,
        "reduced": args.reduced,
    })
    job = con.server.job_creator.from_contract(contract)
    datasets = make_silo_datasets(args.silos, vocab=cfg_r.vocab,
                                  seq_len=args.seq_len, seed=args.seed)
    run_id = con.start(job, datasets)
    t0 = time.time()
    phase = con.run_to_completion()
    rep = run_report(con.server.metadata, run_id)
    print(f"run {run_id}: {phase} in {time.time()-t0:.1f}s")
    print("loss curve:", [round(l, 4) for l in rep["loss_curve"]])
    print("contributions (r0):",
          rep["rounds"][0]["contributions"]["data_size"])
    print("metadata chain ok:", con.server.metadata.verify_chain())
    assert phase == "done"
    return rep


def run_pod(args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import adamw
    from repro.sharding import param_pspecs
    from repro.training import (fedavg_pod_params, make_multipod_train_step)

    n_pods = 2
    mesh = make_host_mesh(data=2, model=2, pod=n_pods)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    opt = adamw(args.lr)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    opt_state = opt.init(params)
    # silo-stacked leaves, sharded P("pod", ...)
    stack = lambda t: jax.tree.map(
        lambda a: jnp.stack([a] * n_pods), t)
    params, opt_state = stack(params), stack(opt_state)
    p_specs = jax.tree.map(lambda s: P("pod", *tuple(s)),
                           param_pspecs(model.abstract_params(), mesh),
                           is_leaf=lambda x: isinstance(x, P))
    shd = lambda t, specs: jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), t, specs,
        is_leaf=lambda x: hasattr(x, "shape"))
    with mesh:
        params = shd(params, p_specs)
        opt_state = shd(opt_state, param_pspecs(opt_state, mesh))
        step = jax.jit(make_multipod_train_step(model, opt, n_pods))
        fedavg = jax.jit(fedavg_pod_params)
        rng = np.random.default_rng(args.seed)
        for i in range(args.steps):
            # per-silo non-IID batches (silo = pod index)
            toks = np.stack([
                rng.integers(0, cfg.vocab, (args.batch_size, args.seq_len))
                + 0 for _ in range(n_pods)]).astype(np.int32)
            batch = {"tokens": jnp.asarray(toks)}
            params, opt_state, metrics = step(params, opt_state, batch)
            if (i + 1) % args.sync_every == 0:
                params = fedavg(params)     # Model Aggregator collective
                tag = " (fedavg)"
            else:
                tag = ""
            print(f"step {i}: loss per silo ="
                  f" {np.asarray(metrics['loss']).round(4)}{tag}")
    print("pod-mode training complete")


def main():
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--mode", choices=["sim", "pod"], default="sim")
    ap.add_argument("--arch", default="fedforecast-100m")
    ap.add_argument("--silos", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-secure", action="store_true")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="use the full (non-reduced) architecture")
    args = ap.parse_args()
    if args.mode == "pod":
        # the (pod, data, model) mesh needs 8 devices: on a CPU host they
        # are forced host devices; set before JAX starts its backend
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.mode == "sim":
        run_sim(args)
    else:
        run_pod(args)


if __name__ == "__main__":
    main()
