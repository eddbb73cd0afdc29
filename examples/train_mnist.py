"""MNIST-style MLP training through the full amp surface.

The rebuild's analog of the reference's runnable example tier
(``examples/imagenet/main_amp.py`` / ``examples/simple``, SURVEY.md §1)
and the BASELINE configs[0] smoke: a 2-layer MLP under
``amp.initialize`` at any opt level, with the dynamic loss scaler
visibly backing off (the contractual "Gradient overflow." line) when an
overflow is injected.

The sandbox has no network access, so the dataset is synthetic
MNIST-shaped data (class-dependent Gaussian blobs, 784 features, 10
classes) — the training dynamics, amp data flow, and observability are
the point, not digit accuracy.

Run::

    python examples/train_mnist.py --opt-level O1
    python examples/train_mnist.py --opt-level O2 --steps 200
    python examples/train_mnist.py --opt-level O1 --inject-inf-at -1  # clean

Works on CPU and on a TPU chip unchanged.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.mlp import MLP
from apex_tpu.optimizers import FusedAdam
from apex_tpu.utils.checkpoint import load_checkpoint, save_checkpoint


def synthetic_mnist(n: int, seed: int = 0):
    """Class-separable 784-d blobs standing in for MNIST."""
    rng = np.random.RandomState(seed)
    centers = rng.randn(10, 784).astype("float32") * 0.5
    labels = rng.randint(0, 10, n)
    images = centers[labels] + rng.randn(n, 784).astype("float32")
    return images, labels


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--opt-level", default="O1",
                    choices=["O0", "O1", "O2", "O3"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--loss-scale", default=None,
                    help='"dynamic" (default per opt level) or a float')
    ap.add_argument("--inject-inf-at", type=int, default=10,
                    help="poison this step's batch with inf to demo the "
                         "scaler backoff; -1 disables")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save a checkpoint at the end / resume from it")
    args = ap.parse_args()

    print(f"backend={jax.default_backend()} opt_level={args.opt_level}")

    model = MLP((784, 256, 10), activation="relu")
    images, labels = synthetic_mnist(4096)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))

    loss_scale = args.loss_scale
    if loss_scale is not None and loss_scale != "dynamic":
        loss_scale = float(loss_scale)
    params, optimizer, handle = amp.initialize(
        params, FusedAdam(lr=args.lr), opt_level=args.opt_level,
        loss_scale=loss_scale)

    opt_state = optimizer.init(params)
    scaler_state = handle.init_state()
    start_step = 0

    if args.ckpt_dir:
        try:
            restored = load_checkpoint(args.ckpt_dir, template=dict(
                params=params, opt_state=opt_state,
                scaler_state=scaler_state))
            params = restored["params"]
            opt_state = restored["opt_state"]
            scaler_state = restored["scaler_state"]
            start_step = restored["_step"]
            print(f"resumed from step {start_step}")
        except FileNotFoundError:
            pass

    compute_dtype = (handle.properties.cast_model_type
                     or handle.properties.compute_dtype or jnp.float32)

    @jax.jit
    def train_step(params, opt_state, scaler_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x.astype(compute_dtype))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

        vg = handle.value_and_grad(loss_fn, scaler_state)
        (loss, found_inf), grads = vg(params)
        new_params, new_opt_state = optimizer.step(
            grads, opt_state, params, skip_if=found_inf)
        new_scaler_state = handle.update_scale(scaler_state, found_inf)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        metrics = handle.scalers[0].metrics(new_scaler_state,
                                            grad_norm=gnorm, loss=loss)
        return new_params, new_opt_state, new_scaler_state, metrics

    nbatches = len(images) // args.batch_size
    metrics = None
    for step in range(start_step, args.steps):
        i = step % nbatches
        x = jnp.asarray(images[i * args.batch_size:(i + 1) * args.batch_size])
        y = jnp.asarray(labels[i * args.batch_size:(i + 1) * args.batch_size])
        if step == args.inject_inf_at:
            x = x.at[0, 0].set(jnp.inf)  # demo: scaler backoff + skip

        prev_scaler_state = scaler_state
        params, opt_state, scaler_state, metrics = train_step(
            params, opt_state, scaler_state, x, y)
        # contractual overflow line, printed host-side (in-graph
        # logging is off by default on accelerators)
        handle.scalers[0].host_overflow_report(prev_scaler_state,
                                               scaler_state)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"scale {float(metrics['loss_scale']):.0f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f} "
                  f"skipped {int(metrics['steps_skipped'])}")

    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.steps, params=params,
                               opt_state=opt_state,
                               scaler_state=scaler_state)
        print(f"checkpoint saved: {path}")

    if metrics is None:  # resumed at or past --steps: nothing to do
        print(f"already trained to step {start_step}")
        return None
    final_loss = float(metrics["loss"])
    print(f"final loss {final_loss:.4f}")
    return final_loss


if __name__ == "__main__":
    main()
