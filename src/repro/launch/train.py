"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 100 --quant hif4 [--ckpt-dir /tmp/ckpt]

Full-size configs on real hardware use the same entry point without
--reduced; the mesh is built from whatever devices the runtime exposes
(data x model), and the step function is the exact one the multi-pod
dry-run lowers.
"""
import argparse

import jax

from repro.configs import get_arch
from repro.core.qlinear import QuantConfig
from repro.jax_setup import configure_jax
from repro.launch.mesh import make_host_mesh
from repro.models.common import ModelCtx
from repro.runtime import TrainLoopConfig, train
from repro.sharding.rules import ShardCtx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--quant", default="hif4")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    configure_jax()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh() if len(jax.devices()) > 1 else None
    ctx = ModelCtx(
        quant=QuantConfig(fmt=args.quant),
        shard=ShardCtx(mesh=mesh),
        remat=not args.reduced,
        attn_q_chunk=min(512, args.seq_len),
        attn_k_chunk=min(1024, args.seq_len),
    )
    _, _, hist = train(cfg, ctx, TrainLoopConfig(
        steps=args.steps, global_batch=args.global_batch,
        seq_len=args.seq_len, checkpoint_dir=args.ckpt_dir,
        num_microbatches=args.microbatches,
    ), on_step=lambda s, st: (
        print(f"step {s:5d} loss {st['loss']:.4f} ({st['time'] * 1e3:.0f}ms)")
        if s % 10 == 0 else None
    ))
    print(f"final loss: {hist['loss'][-1]:.4f}; "
          f"stragglers flagged: {len(hist['stragglers'])}")


if __name__ == "__main__":
    main()
