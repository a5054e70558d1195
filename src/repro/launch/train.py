"""Production training launcher.

Three modes:

  * ``--federated`` — run the full federated simulation (build_experiment
    + FederatedServer.run) with the round driver selected by
    ``--round-driver`` (``device`` scans every round into one compiled
    program per checkpoint segment) and streamed resumable checkpoints
    via ``--checkpoint-to`` / ``--resume-from``.
  * ``--local``  — run real federated fine-tuning on this host's devices
    (CPU in this container) at a reduced scale; this is what the e2e
    example drives.
  * default      — build the production mesh (requires a real multi-host
    TPU slice, or the dry-run's forced host-device count), bind the
    sharded train step for ``--arch``, and run ``--steps`` steps on
    synthetic on-device batches.  In this offline container use
    ``repro.launch.dryrun`` instead, which stops after compile.

  PYTHONPATH=src python -m repro.launch.train --local --arch olmoe-1.3b-6.9b
  PYTHONPATH=src python -m repro.launch.train --federated --clients 64 \
      --rounds 4 --round-driver device --checkpoint-to /tmp/fed.ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import INPUT_SHAPES, ShapeConfig, TrainConfig
from ..configs.registry import get_config
from . import steps as steps_lib
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh, make_production_mesh


def synthetic_batch(cfg, shape, key):
    tshape = ((shape.global_batch, shape.seq_len, cfg.num_codebooks)
              if cfg.num_codebooks else (shape.global_batch, shape.seq_len))
    tokens = jax.random.randint(key, tshape, 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((shape.global_batch, shape.seq_len), jnp.float32)
    return tokens, labels, mask


def run_federated(args) -> None:
    """--federated: assemble an Experiment and run every round through the
    selected round driver, with streamed checkpoints / resume."""
    from ..configs.base import FederatedConfig
    from ..data.synthetic import DataConfig
    from ..federated.simulation import build_experiment

    cfg = get_config(args.arch, args.variant or "smoke")
    fed = FederatedConfig(num_clients=args.clients, rounds=args.rounds,
                          participation=args.participation,
                          round_driver=args.round_driver,
                          checkpoint_every=args.checkpoint_every,
                          seed=args.seed)
    tc = TrainConfig(batch_size=8, local_epochs=1)
    data = DataConfig(vocab_size=cfg.vocab_size,
                      n_examples=max(args.clients * 8, 64),
                      seq_len=64, n_clusters=4)
    exp = build_experiment(cfg, fed=fed, tc=tc, data=data)
    t0 = time.time()
    results = exp.server.run(resume_from=args.resume_from,
                             checkpoint_to=args.checkpoint_to)
    dt = time.time() - t0
    for res in results:
        finite = [l for l in res.client_losses if np.isfinite(l)]
        mean = float(np.mean(finite)) if finite else float("nan")
        print(f"round {res.round_idx}: {len(res.participating)} clients, "
              f"mean loss {mean:.4f}")
    per_round = dt / max(len(results), 1)
    print(f"{len(results)} rounds via {fed.round_driver!r} driver in "
          f"{dt:.2f}s ({per_round:.2f}s/round)")
    print("done")


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1.3b-6.9b")
    ap.add_argument("--variant", default=None,
                    help="full|smoke|swa (default: smoke for --local)")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--k", type=int, default=None,
                    help="FLAME client expert budget k_i")
    # federated-simulation mode
    ap.add_argument("--federated", action="store_true",
                    help="run the federated simulation end-to-end")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--round-driver", default="host",
                    choices=("host", "device"),
                    help="host = per-round Python loop (oracle); device = "
                         "one lax.scan program per checkpoint segment")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="device driver: rounds per checkpoint segment")
    ap.add_argument("--checkpoint-to", default=None)
    ap.add_argument("--resume-from", default=None)
    args = ap.parse_args()

    if args.federated:
        run_federated(args)
        return

    if args.local:
        mesh = make_local_mesh()
        cfg = get_config(args.arch, args.variant or "smoke")
        shape = ShapeConfig("local_train", seq_len=64, global_batch=8,
                            kind="train")
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg = get_config(args.arch, args.variant or "full")
        shape = INPUT_SHAPES[args.shape]

    key = jax.random.PRNGKey(0)
    with mesh:
        bundle = steps_lib.build_train(cfg, shape, mesh, k=args.k,
                                       tc=TrainConfig())
        print(f"{cfg.name} × {shape.name} on {mesh.devices.shape}: "
              f"knobs={bundle.meta}")
        # materialise real state (local mode only — production state comes
        # from the checkpoint/restore path)
        from ..core import lora as lora_lib
        from ..models import model as model_lib
        from ..optim import adam
        params = model_lib.init_params(key, cfg)
        lora = lora_lib.init_lora(jax.random.fold_in(key, 1), cfg, params)
        resc = (lora_lib.init_rescalers(cfg, bundle.meta["k"] or 1)
                if cfg.moe.enabled else None)
        trainable = lora_lib.make_trainable(lora, resc)
        opt = adam.init(trainable)

        for step in range(args.steps):
            tokens, labels, mask = synthetic_batch(
                cfg, shape, jax.random.fold_in(key, 100 + step))
            t0 = time.time()
            trainable, opt, metrics = bundle.fn(params, trainable, opt,
                                                tokens, labels, mask)
            loss = float(metrics["loss"])
            print(f"step {step}: loss {loss:.4f} "
                  f"({time.time() - t0:.2f}s)")
            assert np.isfinite(loss)
    print("done")


if __name__ == "__main__":
    main()
