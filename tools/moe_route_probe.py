#!/usr/bin/env python
"""A group-limited router's selection, several ways, on the live backend:
the probe of PR 56 (PERF.md §6 has the reading).

From ``(T, E)`` float32 router logits (a sigmoid, a selection bias, then
the picks), each form timed ``--reps`` times inside one program with the
picks fed back into the next call's logits behind an optimization barrier:

``today``       the ``lax.top_k`` form the trunk had before PR 56: the
                groups' top-2 by ``top_k`` over ``(T, G, E / G)``, the
                surviving groups by ``top_k`` over ``(T, G)`` and a
                scatter, the picks by ``top_k`` over ``(T, E)``;
``steps12``     ``transformer._surviving_groups`` (maxima and a rank by
                comparison, no sort) and ``top_k`` over ``(T, E)``;
``steps12_arg`` the same with the second largest taken by masking the
                first arg-maximum's lane (a variadic reduce) instead of
                counting the maximum's lanes;
``steps123``    ``_surviving_groups`` and ``top_k_rounds`` below (``k``
                rounds of first arg-maximum and mask): no sort at all.
                NOT in the server: step 0 found the picks' sort at an
                eighth of the sorts' time, under the quarter ISSUE 56 set;
``trunk``       ``transformer._group_limited_select`` as it stands.

With ``G`` 1 (the control, a plain router that must NOT change) the forms
are ``today`` (``top_k`` alone) and ``steps123`` (the rounds alone).

Prints one JSON line a (case, form): us a call and whether the picks are
``today``'s.  Refuses a machine without a TPU unless ``--cpu`` (a rehearsal
at tiny sizes: its times mean nothing).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: (T, E, G, topk_group): a decode step's rows and two packed prefills' of
#: the group-limited cell, and the control
CASES = ((128, 512, 8, 4), (1024, 512, 8, 4), (2048, 512, 8, 4),
         (128, 256, 1, 1))
K = 8


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.models import transformer
    from tpuserve.models.config import get_model_config

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu:
        print("no TPU: this probe measures on the chip only", file=sys.stderr)
        return 1
    base = get_model_config("tiny-ling-hybrid")

    def today(choice, cfg):
        T, E = choice.shape
        G = cfg.moe_n_group
        if G > 1:
            grouped = choice.reshape(T, G, E // G)
            group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, gidx = jax.lax.top_k(group_scores, cfg.moe_topk_group)
            gmask = jnp.zeros_like(group_scores).at[
                jnp.arange(T)[:, None], gidx].set(1.0)
            choice = jnp.where(gmask[..., None] > 0, grouped,
                               0.0).reshape(T, E)
        return jax.lax.top_k(choice, K)[1]

    def groups_by_argmax(choice, cfg):
        T, E = choice.shape
        G = cfg.moe_n_group
        grouped = choice.reshape(T, G, E // G)
        m1 = jnp.max(grouped, axis=-1)
        first = jnp.argmax(grouped, axis=-1)
        lanes = jnp.arange(E // G)
        m2 = jnp.max(jnp.where(lanes == first[..., None], -jnp.inf, grouped),
                     axis=-1)
        scores = m1 + m2
        mine, other = scores[:, :, None], scores[:, None, :]
        g = jnp.arange(G)
        ahead = (other > mine) | ((other == mine) & (g[None, :] < g[:, None]))
        return jnp.sum(ahead, axis=-1, dtype=jnp.int32) < cfg.moe_topk_group

    def top_k_rounds(choice, k):
        """``lax.top_k(choice, k)[1]`` by ``k`` rounds of (first
        arg-maximum, mask): descending, the lower index first among
        equals."""
        lanes = jnp.arange(choice.shape[-1], dtype=jnp.int32)
        picks = []
        for _ in range(k):
            pick = jnp.argmax(choice, axis=-1).astype(jnp.int32)
            picks.append(pick)
            choice = jnp.where(lanes[None, :] == pick[:, None], -jnp.inf,
                               choice)
        return jnp.stack(picks, axis=-1)

    def masked(choice, gmask):
        T, E = choice.shape
        G = gmask.shape[-1]
        return jnp.where(gmask[..., None], choice.reshape(T, G, E // G),
                         0.0).reshape(T, E)

    def steps12(choice, cfg):
        return jax.lax.top_k(
            masked(choice, transformer._surviving_groups(choice, cfg)), K)[1]

    def steps12_arg(choice, cfg):
        return jax.lax.top_k(masked(choice, groups_by_argmax(choice, cfg)),
                             K)[1]

    def steps123(choice, cfg):
        if cfg.moe_n_group > 1:
            choice = masked(choice, transformer._surviving_groups(choice, cfg))
        return top_k_rounds(choice, K)

    def trunk(choice, cfg):
        return transformer._group_limited_select(choice, cfg)[0]

    def timed(form, cfg, logits, bias):
        def picks(logits):
            return form(jax.nn.sigmoid(logits) + bias[None, :], cfg)

        @jax.jit
        def fn(logits):
            def body(_, logits):
                topi = jax.lax.optimization_barrier(picks(logits))
                # the picks move the next call's logits: nothing is hoisted
                return logits + 1e-6 * jnp.sum(topi, axis=-1, keepdims=True
                                               ).astype(logits.dtype)
            return jax.lax.fori_loop(0, args.reps, body, logits)
        x = logits
        for _ in range(2):                      # compile + settle
            x = fn(x)
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            x = fn(x)
        jax.block_until_ready(x)
        sec = (time.perf_counter() - t0) / (args.iters * args.reps)
        return sec, np.asarray(jax.jit(picks)(logits))

    rng = np.random.default_rng(args.seed)
    for T, E, G, topk_group in CASES if on_tpu else (
            (16, 64, 8, 4), (16, 32, 1, 1)):
        cfg = dataclasses.replace(
            base, num_experts=E, num_experts_per_tok=K, moe_n_group=G,
            moe_topk_group=topk_group, moe_scoring="sigmoid",
            moe_experts_held=0, moe_first_expert=0)
        logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
        bias = jnp.asarray(rng.normal(size=(E,)) * 0.1, jnp.float32)
        forms = [("today", today), ("steps123", steps123)]
        if G > 1:
            forms[1:1] = [("steps12", steps12), ("steps12_arg", steps12_arg)]
            forms.append(("trunk", trunk))
        want = None
        for label, form in forms:
            line = {"T": T, "E": E, "G": G, "topk_group": topk_group,
                    "form": label}
            try:
                sec, got = timed(form, cfg, logits, bias)
            except Exception as e:      # a form the compiler refuses
                line["error"] = repr(e)[:300]
                print(json.dumps(line), flush=True)
                continue
            if want is None:
                want = got
            line.update({"us": round(sec * 1e6, 2),
                         "as_today": bool(np.array_equal(got, want)),
                         "device": jax.devices()[0].device_kind})
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
