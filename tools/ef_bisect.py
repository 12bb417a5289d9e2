"""Bisect the Random-K + error-feedback + momentum divergence (VERDICT r1 #3).

Round-1 observation (`benchmarks/convergence_r1.txt`): wire/simulate Random-K
k=1% WITH error feedback diverges (NaN) under the dawn protocol's momentum-0.9
Nesterov SGD, while Top-K+EF and Block-Top-K+EF converge, and momentum=0 or
EF-off converge.  The reference trains its `RandomKSparsifiedDDP` (EF +
Random-K, `IMAGENET/training/sparsified_ddp.py:408-413`) with momentum-0.9 SGD
(`train_imagenet_nv.py:186-191`) — so either our composition differs, or the
reference's would diverge under the same (CIFAR dawn, high peak lr, Nesterov)
protocol too.

This tool reproduces the dynamics small and fast — one worker, a 2-layer MLP
on non-saturating synthetic data, jitted `lax.scan` over steps — and sweeps
the suspects:

  * momentum value (0 / 0.9)
  * Nesterov on/off (dawn uses Nesterov, `dawn.py:146-148`; the ImageNet
    harness uses plain momentum)
  * EF accumulation style:
      - 'plain'    residual += dropped gradient (the reference rule)
      - 'momentum' DGC-style momentum-corrected EF (Lin et al., ICLR'18
        "Deep Gradient Compression", PAPERS.md): accumulate the *velocity*
        v = mu v + g instead of the raw gradient, send sparse(residual),
        and apply the payload WITHOUT optimizer momentum — momentum lives
        inside the compression stream, so delayed coordinates do not get
        double-amplified by the optimizer's momentum buffer.
  * method: randomk / topk (topk is the converging control)
  * peak lr scale

Also runs the same protocol through a *torch* implementation mirroring the
reference's update rule (masked_select/masked_fill EF + torch.optim.SGD) to
show whether the reference's own arithmetic shares the divergence.

Usage:
    python tools/ef_bisect.py            # full bisect table
    python tools/ef_bisect.py --steps 640 --peak_lr 0.4
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def make_data(seed: int = 0, n: int = 4096, dim: int = 64, classes: int = 10,
              noise_frac: float = 0.15):
    """Teacher-labelled gaussian features + label noise: a task a small MLP
    fits to ~90%, not 100% — gradients stay non-trivial all run."""
    rng = np.random.RandomState(seed)
    x = rng.randn(n, dim).astype(np.float32)
    w_t = rng.randn(dim, classes).astype(np.float32)
    y = np.argmax(x @ w_t + 0.5 * rng.randn(n, classes), axis=1)
    flip = rng.rand(n) < noise_frac
    y[flip] = rng.randint(0, classes, flip.sum())
    return x, y.astype(np.int32)


# ---------------------------------------------------------------- JAX side

def lr_value(schedule: str, peak_lr: float, steps: int, batch: int,
             step: int) -> float:
    """Per-step lr in summed-loss units — ONE scalar implementation consumed
    by both the JAX arm (via a host-built table) and the torch arm, so the
    two bisect arms can never train under different curves.

    'dawn'  — the CIFAR protocol's triangle: ramp to peak at 1/8, anneal to 0
              (`dawn.py:110`).
    'step'  — the reference's ImageNet shape (`IMAGENET/train.py:60-72`):
              linear warmup over the first 1/8, flat at peak to 60%, peak/10
              to 85%, peak/100 after — the regime the reference actually ran
              `RandomKSparsifiedDDP` under (`train_imagenet_nv.py:203-222`).
    """
    warm = max(1, steps // 8)
    if schedule == "dawn":
        return max(min(peak_lr * step / warm,
                       peak_lr * (steps - step) / (steps - warm)), 0.0) / batch
    if schedule != "step":
        raise ValueError(f"unknown schedule {schedule!r}")
    if step < warm:
        return peak_lr * step / warm / batch
    if step < 0.6 * steps:
        return peak_lr / batch
    if step < 0.85 * steps:
        return peak_lr / 10.0 / batch
    return peak_lr / 100.0 / batch


def make_lr_fn(schedule: str, peak_lr: float, steps: int, batch: int):
    """Traced-step lr lookup for the JAX arm: the scalar schedule evaluated
    on host into a table, indexed inside `lax.scan`."""
    import jax.numpy as jnp

    table = jnp.asarray([lr_value(schedule, peak_lr, steps, batch, s)
                         for s in range(steps)], jnp.float32)
    return lambda step: table[step]


def run_jax(momentum: float, nesterov: bool, ef: bool, ef_style: str,
            method: str, ratio: float, steps: int, peak_lr: float,
            batch: int = 512, seed: int = 0, clip: float = 0.0,
            warmup_sparsity: bool = False, schedule: str = "dawn"):
    """Train the MLP under the dawn summed-loss protocol; return per-step loss."""
    import jax
    import jax.numpy as jnp

    x_np, y_np = make_data(seed)
    n, dim = x_np.shape
    classes = int(y_np.max()) + 1
    hidden = 128
    rng = np.random.RandomState(seed + 1)
    params = {
        "w1": jnp.asarray(rng.randn(dim, hidden).astype(np.float32) / np.sqrt(dim)),
        "w2": jnp.asarray(rng.randn(hidden, classes).astype(np.float32) / np.sqrt(hidden)),
    }
    x_all, y_all = jnp.asarray(x_np), jnp.asarray(y_np)

    # dawn protocol scaling (`dawn.py:142-148`): summed loss, lr/bs, wd*bs
    wd = 5e-4 * batch
    lr_at = make_lr_fn(schedule, peak_lr, steps, batch)

    def loss_fn(p, xb, yb):
        h = jnp.maximum(xb @ p["w1"], 0.0)
        logits = h @ p["w2"]
        logz = jax.nn.log_softmax(logits)
        return -jnp.sum(jnp.take_along_axis(logz, yb[:, None], 1))


    def compress(flat, key, step):
        n_el = flat.shape[0]
        if warmup_sparsity:
            # DGC-style sparsity warm-up: keep-ratio decays exponentially
            # from dense to the target over the first quarter of training
            frac = jnp.clip(step / (steps / 4.0), 0.0, 1.0)
            ratio_t = jnp.exp(jnp.log(1.0) * (1 - frac) + jnp.log(ratio) * frac)
        else:
            ratio_t = ratio
        if method == "randomk":
            if warmup_sparsity:
                mask = jax.random.uniform(key, (n_el,)) < ratio_t
            else:
                k = max(1, int(round(ratio * n_el)))
                idx = jax.random.permutation(key, n_el)[:k]
                mask = jnp.zeros(n_el, bool).at[idx].set(True)
        else:  # topk
            k = max(1, int(round(ratio * n_el)))
            t = jnp.sort(jnp.abs(flat))[n_el - k]
            mask = jnp.abs(flat) >= t
        return jnp.where(mask, flat, 0.0), mask

    def step_fn(carry, step):
        p, mom, resid, vel, key = carry
        key, k1, k2 = jax.random.split(key, 3)
        i = jax.random.randint(k1, (batch,), 0, n)
        g = jax.grad(loss_fn)(p, x_all[i], y_all[i])

        lr = lr_at(step)
        new_p, new_mom, new_resid, new_vel = {}, {}, {}, {}
        for name in p:
            gl = g[name].reshape(-1)
            if clip > 0:
                # DGC-style gradient clipping before EF accumulation, in
                # mean-loss units (gl is a summed-loss gradient)
                gnorm = jnp.linalg.norm(gl) / batch
                gl = gl * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
            if ef and ef_style == "ef21":
                # EF21 (Richtarik et al., 2021): each worker keeps a gradient
                # estimate h and transmits only the compressed *innovation*
                # c = compress(g - h); h += c.  The optimizer consumes the
                # smooth dense estimate h — momentum never sees delayed
                # spikes, which is exactly what blows plain-EF Random-K up.
                innov = gl - resid[name]              # resid doubles as h
                sent, mask = compress(innov, jax.random.fold_in(k2, hash(name) % 997), step)
                h = resid[name] + sent
                d = h + wd * p[name].reshape(-1)
                buf = momentum * mom[name] + d
                upd = d + momentum * buf if nesterov else buf
                new_p[name] = (p[name].reshape(-1) - lr * upd).reshape(p[name].shape)
                new_mom[name] = buf
                new_resid[name], new_vel[name] = h, vel[name]
            elif ef and ef_style == "momentum":
                # DGC (Lin et al.): velocity accumulates into the residual;
                # the optimizer applies the sparse payload directly (no second
                # momentum), and — critically — the *velocity is also masked*
                # at sent coordinates ("momentum factor masking"), so stale
                # momentum stops re-injecting directions that already shipped.
                v = momentum * vel[name] + gl
                acc = resid[name] + v
                sent, mask = compress(acc, jax.random.fold_in(k2, hash(name) % 997), step)
                r = jnp.where(mask, 0.0, acc)
                v = jnp.where(mask, 0.0, v)
                d = sent + wd * p[name].reshape(-1)
                new_p[name] = (p[name].reshape(-1) - lr * d).reshape(p[name].shape)
                new_mom[name] = mom[name]
                new_resid[name], new_vel[name] = r, v
            else:
                acc = (resid[name] + gl) if ef else gl
                sent, mask = compress(acc, jax.random.fold_in(k2, hash(name) % 997), step)
                r = jnp.where(mask, 0.0, acc) if ef else resid[name]
                if ef_style == "clip_sent":
                    # clip the aggregated sparse update itself: bounds the
                    # ~1/k-step residual spike, which local-gradient clipping
                    # cannot (the residual accumulates clipped inflow for
                    # 1/k steps and still releases it at once)
                    snorm = jnp.linalg.norm(sent) / batch
                    sent = sent * jnp.minimum(1.0, 1.0 / jnp.maximum(snorm, 1e-12))
                d = sent + wd * p[name].reshape(-1)
                buf = momentum * mom[name] + d
                upd = d + momentum * buf if nesterov else buf
                new_p[name] = (p[name].reshape(-1) - lr * upd).reshape(p[name].shape)
                new_mom[name] = buf
                new_resid[name], new_vel[name] = r, vel[name]
        lval = loss_fn(p, x_all[i], y_all[i]) / batch
        return (new_p, new_mom, new_resid, new_vel, key), lval

    import jax
    zeros = {k: jnp.zeros(v.size) for k, v in params.items()}
    carry = (params, dict(zeros), dict(zeros), dict(zeros), jax.random.key(seed))
    carry, losses = jax.lax.scan(step_fn, carry, jnp.arange(steps))
    return np.asarray(losses)


# -------------------------------------------------------------- torch side

def run_torch(momentum: float, nesterov: bool, ratio: float, steps: int,
              peak_lr: float, batch: int = 512, seed: int = 0,
              schedule: str = "dawn"):
    """The reference's own arithmetic: per-parameter Random-K EF via
    masked_select/masked_fill (`sparsified_ddp.py:408-413`) + torch.optim.SGD
    momentum (`train_imagenet_nv.py:186-191`), world size 1."""
    import torch

    torch.manual_seed(seed)
    x_np, y_np = make_data(seed)
    x = torch.tensor(x_np)
    y = torch.tensor(y_np, dtype=torch.long)
    n, dim = x.shape
    classes = int(y.max().item()) + 1
    model = torch.nn.Sequential(
        torch.nn.Linear(dim, 128, bias=False),
        torch.nn.ReLU(),
        torch.nn.Linear(128, classes, bias=False),
    )
    wd = 5e-4 * batch
    opt = torch.optim.SGD(model.parameters(), lr=0.0, momentum=momentum,
                          nesterov=nesterov and momentum > 0, weight_decay=wd)
    crit = torch.nn.CrossEntropyLoss(reduction="sum")
    eps = [torch.zeros(p.numel()) for p in model.parameters()]
    gen = torch.Generator().manual_seed(2147483647)  # the reference seed
    losses = []
    for step in range(steps):
        lr = lr_value(schedule, peak_lr, steps, batch, step)
        for gparam in opt.param_groups:
            gparam["lr"] = lr
        i = torch.randint(0, n, (batch,))
        opt.zero_grad()
        loss = crit(model(x[i]), y[i])
        loss.backward()
        with torch.no_grad():
            for p, e in zip(model.parameters(), eps):
                flat = p.grad.reshape(-1)
                flat += e                                     # EF in
                k = max(1, int(round(ratio * flat.numel())))
                mask = torch.randperm(flat.numel(), generator=gen).lt(k)
                e.copy_(flat.masked_fill(mask, 0))            # EF out
                flat.mul_(mask)                               # sparse grad
        opt.step()
        losses.append(loss.item() / batch)
        if not np.isfinite(losses[-1]):
            break
    return np.asarray(losses)


def summarize(name: str, losses: np.ndarray) -> str:
    bad = np.where(~np.isfinite(losses) | (losses > 1e4))[0]
    if bad.size:
        return (f"{name:58s} DIVERGED (loss non-finite/blown-up at step "
                f"{bad[0]}/{len(losses)})")
    return (f"{name:58s} ok   final={losses[-1]:.4f}  "
            f"max={losses.max():.2f}  last10={losses[-10:].mean():.4f}")


def run_operating_point(args):
    """VERDICT r2 #1: map the reference's ACTUAL operating regime — the
    ImageNet step schedule (`IMAGENET/train.py:60-72`), not just dawn's
    triangle — over peak lr x EF flavor, all at momentum 0.9 (the reference's
    `--momentum` default, `train_imagenet_nv.py:48`), Random-K k=1% + EF."""
    rows = []
    print(f"# operating-point map: schedule={args.schedule} steps={args.steps} "
          f"k={args.ratio}", flush=True)
    for peak in (0.4, 0.2, 0.1, 0.05, 0.02):
        dense = run_jax(0.9, True, False, "plain", "randomk", 1.0, args.steps,
                        peak, schedule=args.schedule)
        rows.append(summarize(f"dense       mom=.9 peak={peak}", dense))
        print(rows[-1], flush=True)
        for label, style, clip, warm in (
            ("plain-EF   ", "plain", 0.0, False),
            ("plain-EF+clip", "plain", 1.0, False),
            ("DGC        ", "momentum", 0.0, False),
            ("DGC+warmup ", "momentum", 0.0, True),
            ("plain+warmup", "plain", 0.0, True),
        ):
            losses = run_jax(0.9, True, True, style, "randomk", args.ratio,
                             args.steps, peak, clip=clip, warmup_sparsity=warm,
                             schedule=args.schedule)
            rows.append(summarize(
                f"randomk+{label} mom=.9 peak={peak}", losses))
            print(rows[-1], flush=True)
        if not args.skip_torch:
            losses = run_torch(0.9, True, args.ratio, args.steps, peak,
                               schedule=args.schedule)
            rows.append(summarize(
                f"TORCH ref-rule randomk+EF mom=.9 peak={peak}", losses))
            print(rows[-1], flush=True)
    return rows


def main(argv=None):
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=640)
    ap.add_argument("--peak_lr", type=float, default=0.4)
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--skip_torch", action="store_true")
    ap.add_argument("--schedule", choices=["dawn", "step"], default="dawn",
                    help="'step' = the reference's ImageNet warmup->step-decay "
                         "shape (train.py:60-72)")
    ap.add_argument("--operating_point", action="store_true",
                    help="sweep peak lr x EF flavor at momentum 0.9 under "
                         "--schedule (VERDICT r2 #1)")
    args = ap.parse_args(argv)

    if args.operating_point:
        return run_operating_point(args)

    rows = []
    cases = [
        # (label, momentum, nesterov, ef, ef_style, method)
        ("dense-ctl   mom=.9 nesterov", None, None, None, None, "dense"),
        ("randomk+EF  mom=.9 nesterov  [r1 diverger]", 0.9, True, True, "plain", "randomk"),
        ("randomk+EF  mom=.9 plain-momentum", 0.9, False, True, "plain", "randomk"),
        ("randomk+EF  mom=0", 0.0, False, True, "plain", "randomk"),
        ("randomk     mom=.9 nesterov  no-EF", 0.9, True, False, "plain", "randomk"),
        ("topk+EF     mom=.9 nesterov  [r1 converger]", 0.9, True, True, "plain", "topk"),
        ("randomk+EF-momentum(DGC) mu=.9", 0.9, False, True, "momentum", "randomk"),
        ("randomk+EF21 mom=.9 nesterov", 0.9, True, True, "ef21", "randomk"),
        ("topk+EF21    mom=.9 nesterov", 0.9, True, True, "ef21", "topk"),
    ]
    clip_cases = [
        # clip the SENT (aggregated sparse) update instead of the local grad
        ("randomk+EF mom=.9 nesterov CLIP-SENT=1", 0.9, True, "clip_sent", "randomk", 0.0, False),
        ("randomk+EF mom=.9 CLIP-SENT + CLIP-local", 0.9, True, "clip_sent", "randomk", 1.0, False),
        # (label, momentum, nesterov, ef_style, method, clip, warmup)
        ("randomk+EF mom=.9 nesterov CLIP=1", 0.9, True, "plain", "randomk", 1.0, False),
        ("randomk+EF mom=.9 nesterov CLIP=1 +WARMUP", 0.9, True, "plain", "randomk", 1.0, True),
        ("randomk+EF mom=.9 nesterov WARMUP only", 0.9, True, "plain", "randomk", 0.0, True),
        ("topk+EF    mom=.9 nesterov CLIP=1", 0.9, True, "plain", "topk", 1.0, False),
    ]
    for label, mom, nest, ef, style, method in cases:
        if method == "dense":
            losses = run_jax(0.9, True, False, "plain", "randomk", 1.0,
                             args.steps, args.peak_lr, schedule=args.schedule)
        else:
            losses = run_jax(mom, nest, ef, style, method, args.ratio,
                             args.steps, args.peak_lr, schedule=args.schedule)
        rows.append(summarize(label, losses))
        print(rows[-1], flush=True)
    for label, mom, nest, style, method, clip, warm in clip_cases:
        losses = run_jax(mom, nest, True, style, method, args.ratio,
                         args.steps, args.peak_lr, clip=clip,
                         warmup_sparsity=warm, schedule=args.schedule)
        rows.append(summarize(label, losses))
        print(rows[-1], flush=True)

    if not args.skip_torch:
        for label, mom, nest in [
            ("TORCH reference-rule randomk+EF mom=.9 nesterov", 0.9, True),
            ("TORCH reference-rule randomk+EF mom=.9 plain", 0.9, False),
            ("TORCH reference-rule randomk+EF mom=0", 0.0, False),
        ]:
            losses = run_torch(mom, nest, args.ratio, args.steps,
                               args.peak_lr, schedule=args.schedule)
            rows.append(summarize(label, losses))
            print(rows[-1], flush=True)
    return rows


if __name__ == "__main__":
    main(sys.argv[1:])
