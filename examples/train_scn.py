"""End-to-end driver: train the SCN U-Net on synthetic labelled scenes.

The paper's workload (3D semantic segmentation) learning on the sparse-conv
stack; scene metadata is built once per scene as an engine ScenePlan and
reused by every step. Run:
    PYTHONPATH=src python examples/train_scn.py [--steps 300] [--res 32]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.data.scenes import N_CLASSES, make_scene
from repro.launch.compile_cache import use_compile_cache
from repro.models.scn import UNetConfig, init_unet, miou, segmentation_loss
from repro.sparse.tensor import SparseVoxelTensor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--res", type=int, default=32)
    ap.add_argument("--cap", type=int, default=4096)
    ap.add_argument("--scenes", type=int, default=8)
    args = ap.parse_args()
    use_compile_cache()

    cfg = UNetConfig(widths=(16, 32, 48), reps=1, resolution=args.res,
                     capacity=args.cap, n_classes=N_CLASSES)
    # pre-build a small dataset of scenes + plans (AdMAC pass per scene)
    data = []
    for s in range(args.scenes):
        coords, feats, labels, mask = make_scene(s, args.res, args.cap)
        t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                              jnp.asarray(mask))
        plan = engine.build_scene_plan(t, cfg, plan_tiles=False)
        data.append((t, plan, jnp.asarray(labels)))
    params = init_unet(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, feats, plan, labels, mask):
        return segmentation_loss(engine.apply_unet(p, feats, plan),
                                 labels, mask)

    grads = [jax.jit(jax.value_and_grad(
        lambda p, f, lbl, pl=plan: loss_fn(p, f, pl, lbl, pl.levels[0].mask),
        has_aux=True)) for _, plan, _ in data]

    lr = 0.3
    t0 = time.time()
    for step in range(args.steps):
        t, plan, labels = data[step % len(data)]
        (loss, acc), g = grads[step % len(data)](params, t.feats, labels)
        params = jax.tree.map(lambda p, gr: p - lr * gr, params, g)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(loss):.4f} acc {float(acc):.3f} "
                  f"({time.time() - t0:.0f}s)")

    # held-out scene
    coords, feats, labels, mask = make_scene(999, args.res, args.cap)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    plan = engine.build_scene_plan(t, cfg, plan_tiles=False)
    pred = np.asarray(jnp.argmax(engine.apply_unet(params, t.feats, plan), -1))
    m = miou(pred, labels, mask, N_CLASSES)
    print(f"held-out mIoU: {m:.3f}")


if __name__ == "__main__":
    main()
