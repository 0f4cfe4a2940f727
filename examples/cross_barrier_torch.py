"""Cross-barrier training for torch models — the reference's
``benchmark_cross_barrier_byteps.py`` pattern on the TPU build's PS
plane: no per-step gradient barrier.  Backward hooks launch one async
push_pull per parameter (front layers highest priority) and the NEXT
forward's module pre-hooks block only on that module's own parameters,
so step N+1's front layers compute while step N's back-layer gradients
are still on the wire (OSDI'20 §5).

Single process (PS hop = identity):

    python examples/cross_barrier_torch.py --steps 30

Distributed: launch scheduler/server/workers with DMLC_* env
(``python -m byteps_tpu.launcher.launch``); runs unchanged.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--opt", default="sgd", choices=["sgd", "adam", "rmsprop"])
    args = ap.parse_args()

    import torch

    import byteps_tpu as bps
    from byteps_tpu.torch.cross_barrier import CrossBarrier

    bps.init()
    torch.manual_seed(0)
    layers = []
    for _ in range(args.depth):
        layers += [torch.nn.Linear(args.width, args.width), torch.nn.ReLU()]
    layers.append(torch.nn.Linear(args.width, 10))
    model = torch.nn.Sequential(*layers)
    opt = CrossBarrier(model, args.opt, lr=0.05)

    g = torch.Generator().manual_seed(1)
    x = torch.randn(args.batch, args.width, generator=g)
    y = 0.1 * torch.randn(args.batch, 10, generator=g)

    t0 = time.perf_counter()
    # the canonical loop: NO optimizer.step(), NO zero_grad — the next
    # forward's pre-hooks wait/apply per module, and CrossBarrier zeroes
    # each gradient as it consumes it
    for step in range(args.steps):
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        if step % 10 == 0 or step == args.steps - 1:
            print(f"[rank {bps.rank()}] step {step:3d} "
                  f"loss {float(loss.detach()):.6f}")
    opt.step()  # final barrier before leaving the loop
    dt = (time.perf_counter() - t0) / args.steps
    print(f"[rank {bps.rank()}] {dt * 1e3:.2f} ms/step, "
          f"{opt.outstanding()} handles outstanding (must be 0)")
    bps.shutdown()


if __name__ == "__main__":
    main()
