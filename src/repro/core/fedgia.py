"""FedGiA — the paper's Algorithm 1, as a composable JAX module.

One communication round = one jitted call:

  1. aggregate   x̄ = (1/m) Σ z_i              (eq. 11 — ONE all-reduce)
  2. grads       ḡ_i = (1/m) ∇f_i(x̄)          (computed ONCE per round)
  3. split       C ~ alpha·m clients            (selection.py; an
     engine-provided participation mask, when present, IS this split)
  4. ADMM branch (i ∈ C):  k0 iterations of eqs (12)-(14)
     GD branch   (i ∉ C):  eqs (15)-(17), once
  5. state carries (z_i, π_i) per client; x_i = z_i − π_i/σ is derived.

Because x̄ and ḡ_i are FIXED within a round, the ADMM iteration is affine
in π_i:  π ← (1−σD)π − σDḡ  with D = (H/m + σI)^{-1}.  `collapsed=True`
(beyond-paper, DESIGN §6 B1) evaluates the k0-step recursion in closed form

    π^{k0} = a^{k0} (π⁰ + ḡ) − ḡ,      a = 1 − σD
    x^{k0} = x̄ − D a^{k0−1} (π⁰ + ḡ)
    z^{k0} = x^{k0} + π^{k0}/σ

— exactly equal to the unrolled loop (property-tested), with ~k0× less
elementwise HBM traffic. `collapsed=False` runs the paper-faithful
`lax.scan`. H policies: scalar r̂·I, clipped diagonal EMA, or the client
Gram matrix (paper's FedGiA_G, linear models).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.config import FedConfig
from repro.core import api, compress, hparams, selection
from repro.core.api import LossFn, broadcast_clients, per_client_value_and_grad
from repro.kernels.fedgia_update import fedgia_update_flat, kernel_by_default
from repro.utils import pytree as pt

LIPSCHITZ_BLOCK = 4096  # clients per vmapped block of the init-time r_i


class FedGiA:
    name = "fedgia"
    # leaves with a leading client axis — what the engine shards over `data`
    # ("ef" = the error-feedback residual buffer, present only under a
    # lossy compressor with error_feedback — absent keys cost nothing)
    client_state_keys = ("z", "pi", "h", "gram_chol", "ef", "fault_prev")
    # model-shaped state the flat engine ravels into (m, N) / (N,) buffers
    # (gram_chol is client-stacked but not model-shaped: it stays a
    # (m, n, n) factor either way)
    flat_client_keys = ("z", "pi", "h", "ef", "fault_prev")
    flat_global_keys = ("x",)
    # FedGiA's GD branch (eqs. 15-17) rewrites EVERY non-selected client's
    # state from its fresh gradient each round, so the round's working set
    # is the whole population by construction — the active tile is (m, N)
    # and the store degenerates to dense (see round_flat_active).
    active_tile = "population"

    def __init__(self, fed: FedConfig, loss_fn: LossFn, model=None):
        self.fed = fed
        self.loss_fn = loss_fn
        self.model = model
        self._vg = per_client_value_and_grad(loss_fn)
        # stale-x̄ rounds: each client's gradient at its OWN anchor
        self._vg_per_anchor = api.per_client_value_and_grad_stacked(loss_fn)

    # ------------------------------------------------------------------ init
    def init(self, params0, rng, init_batch=None) -> Dict[str, Any]:
        fed = self.fed
        m = fed.num_clients
        sdt = jnp.dtype(fed.state_dtype)
        r = jnp.float32(fed.lipschitz)
        if fed.auto_lipschitz and init_batch is not None:
            per_client = jax.vmap(
                lambda b, k: hparams.estimate_lipschitz(
                    self.loss_fn, params0, b, k
                ),
                in_axes=(0, 0),
            )
            r = per_client(init_batch, jax.random.split(rng, m)).max()
        elif self.model is not None and hasattr(self.model, "lipschitz") and init_batch is not None:
            # in blocks of clients: one vmap over m=10^6 per-client
            # spectral norms needs tens of GB of SVD temporaries
            r = jax.lax.map(self.model.lipschitz, init_batch,
                            batch_size=LIPSCHITZ_BLOCK).max()

        # paper §V.B: x_i^0 = pi_i^0 = 0; we start from params0 instead of 0
        # so the same init works for NNs (paper setting recovered with
        # params0 = zeros).
        xc = broadcast_clients(pt.tree_cast(params0, sdt), m)
        pi = pt.tree_zeros_like(xc)
        z = xc  # z = x + pi/sigma with pi = 0

        state: Dict[str, Any] = {
            "x": pt.tree_cast(params0, sdt),
            "z": z,
            "pi": pi,
            "sigma": jnp.float32(hparams.sigma_from(fed.sigma_t, r, m)),
            "r": r,
            "round": jnp.zeros((), jnp.int32),
            "rng": rng,
        }
        if fed.h_policy == "diag_ema":
            state["h"] = jax.tree.map(
                lambda a: jnp.full(a.shape, r, jnp.float32), xc
            )
        elif fed.h_policy == "gram":
            assert self.model is not None and hasattr(self.model, "gram"), (
                "gram H policy requires a model exposing .gram(batch) "
                "(linear models, paper Table III)"
            )
            assert init_batch is not None
            H = jax.vmap(self.model.gram)(init_batch)  # (m, n, n)
            sig = hparams.sigma_from(fed.sigma_t, r, m)
            n = H.shape[-1]
            A = H / m + sig * jnp.eye(n)
            state["gram_chol"] = jax.vmap(lambda a: jsl.cho_factor(a)[0])(A)
        return state

    # ------------------------------------------------------------- internals
    def _apply_Dinv(self, state, v):
        """v -> (H/m + sigma I)^{-1} v, stacked over clients."""
        fed, m = self.fed, self.fed.num_clients
        sigma = state["sigma"]
        if fed.h_policy == "gram":
            chol = state["gram_chol"]
            flat = v["x"]  # (m, n) — gram restricted to linear models
            out = jax.vmap(lambda c, b: jsl.cho_solve((c, False), b))(chol, flat)
            return {"x": out}
        h = state.get("h")
        if h is None:  # scalar policy: H = r I
            return jax.tree.map(lambda g: g / (state["r"] / m + sigma), v)
        return jax.tree.map(lambda g, hh: g / (hh / m + sigma), v, h)

    def _admm_branch(self, state, xbar_c, gbar):
        """k0 iterations of eqs (12)-(14) for ALL clients (masked later)."""
        fed = self.fed
        sigma = state["sigma"]
        pi0 = state["pi"]
        base = pt.tree_add(pi0, gbar)  # pi^0 + g

        if fed.collapsed and fed.h_policy != "gram":
            m = fed.num_clients
            h = state.get("h")

            def leafwise(g, p0, hh):
                d = 1.0 / (hh / m + sigma)
                a = 1.0 - sigma * d
                b = p0 + g
                ak1 = a ** (fed.k0 - 1)
                pi_new = ak1 * a * b - g
                x_new = -d * ak1 * b  # relative to xbar
                return x_new, pi_new

            if h is None:
                r = state["r"]
                hs = jax.tree.map(lambda g: r, gbar)
            else:
                hs = h
            xn_rel = jax.tree.map(lambda g, p0, hh: leafwise(g, p0, hh)[0], gbar, pi0, hs)
            pi_new = jax.tree.map(lambda g, p0, hh: leafwise(g, p0, hh)[1], gbar, pi0, hs)
            x_new = pt.tree_add(xbar_c, xn_rel)
        else:
            # paper-faithful k0-step iteration. Python loop (k0 is small):
            # keeps XLA cost_analysis exact (scan bodies are counted once).
            pi_after = pi0
            for _ in range(fed.k0 - 1):
                x = pt.tree_sub(
                    xbar_c, self._apply_Dinv(state, pt.tree_add(gbar, pi_after))
                )
                pi_after = pt.tree_axpy(sigma, pt.tree_sub(x, xbar_c), pi_after)
            x_new = pt.tree_sub(
                xbar_c, self._apply_Dinv(state, pt.tree_add(gbar, pi_after))
            )
            pi_new = pt.tree_axpy(sigma, pt.tree_sub(x_new, xbar_c), pi_after)

        z_new = pt.tree_axpy(1.0 / sigma, pi_new, x_new)
        return x_new, pi_new, z_new

    def _apply_Dinv_flat(self, state, v, spec):
        """Flat-buffer (m, N) twin of `_apply_Dinv` — same op order, so the
        unrolled flat iteration is bitwise the unrolled pytree iteration
        on the raveled layout."""
        fed, m = self.fed, self.fed.num_clients
        sigma = state["sigma"]
        if fed.h_policy == "gram":
            chol = state["gram_chol"]
            n = spec.size  # gram is restricted to single-leaf linear models
            flat = v[:, :n]
            out = jax.vmap(lambda c, b: jsl.cho_solve((c, False), b))(chol, flat)
            pad = v.shape[1] - n
            return jnp.pad(out, ((0, 0), (0, pad))) if pad else out
        h = state.get("h")
        if h is None:  # scalar policy: H = r I
            return v / (state["r"] / m + sigma)
        return v / (h / m + sigma)

    def _admm_branch_flat(self, state, xbar_c, gbar, spec):
        """k0 iterations of eqs (12)-(14) on the flat (m, N) buffer.

        Mirrors `_admm_branch` operation-for-operation (division by
        (h/m + sigma), add-of-negated relative step, axpy z), so the
        non-kernel flat branch is bitwise the pytree branch on the
        raveled layout."""
        fed = self.fed
        sigma = state["sigma"]
        pi0 = state["pi"]

        if fed.collapsed and fed.h_policy != "gram":
            m = fed.num_clients
            h = state.get("h")
            hh = state["r"] if h is None else h
            d = 1.0 / (hh / m + sigma)
            a = 1.0 - sigma * d
            b = pi0 + gbar
            ak1 = a ** (fed.k0 - 1)
            pi_new = ak1 * a * b - gbar
            x_new = xbar_c + (-d * ak1 * b)
        else:
            pi_after = pi0
            for _ in range(fed.k0 - 1):
                x = xbar_c - self._apply_Dinv_flat(state, gbar + pi_after,
                                                   spec)
                pi_after = sigma * (x - xbar_c) + pi_after
            x_new = xbar_c - self._apply_Dinv_flat(state, gbar + pi_after,
                                                   spec)
            pi_new = sigma * (x_new - xbar_c) + pi_after

        z_new = (1.0 / sigma) * pi_new + x_new
        return x_new, pi_new, z_new

    def _use_kernel(self) -> bool:
        """Route the collapsed diagonal-H branch through the batched Pallas
        kernel? `FedConfig.use_kernel`: None = auto by backend."""
        fed = self.fed
        if not fed.collapsed or fed.h_policy == "gram":
            return False
        if fed.use_kernel is None:
            return kernel_by_default()
        return fed.use_kernel

    # ------------------------------------------------------------ flat round
    def round_flat(self, state, batch, spec, mask=None, stale=None,
                   compressor=None, donate_kernel=False,
                   faults=None, screening=None):
        """One communication round on the FLAT client-state buffer.

        Same contract as `round`, but `state["z"]` / `state["pi"]` /
        `state["h"]` are one (m, N) buffer each (`state["x"]` is (N,)),
        raveled once by the engine (`utils.pytree.RavelSpec`). Eq. (11)
        is a mean over a single contiguous array — under sharding the
        round's ONE model-size all-reduce — and the ADMM/GD branch is a
        single fused elementwise pass: the batched Pallas
        `kernels/fedgia_update` kernel when `FedConfig.use_kernel`
        resolves true (fp-equivalent), else a jnp twin that is bitwise
        the pytree branch on the raveled layout. The pytree is
        reconstructed only for the per-client gradient evaluation and the
        `grad_sq_norm` metric boundary (docs/engine.md).

        `compressor` (core/compress.py): eq. (11) aggregates the DECODED
        uploads C(z_i [+ e_i]) instead of the raw z_i — FedGiA's uplink
        is the whole population's z every round (every client's state is
        rewritten, `active_tile="population"`), so the codec runs on all
        m rows and, with error feedback, every residual advances every
        round. Decompress-before-reduce: the fp32 decode enters the same
        one-psum mean.

        Overlap (`run_rounds(overlap="scatter")`): when the engine seeds
        `state["ovl_shard"]`, eq. (11)'s collective is SPLIT across the
        round boundary — the round top all-gathers last round's
        reduce-scattered consensus shard (`api.flat_overlap_consensus`)
        instead of computing the mean, and the round end reduce-scatters
        the FRESH z upload (`api.flat_overlap_aggregate`), so the wire
        hides behind the next round's local compute. Value-preserving:
        x̄ᵗ is the same mean either way (bitwise when unsharded); the
        codec key at the round end is round t+1's barrier key, so only
        the round-0 slot seed + the error-feedback sequence shift for
        lossy codecs (docs/engine.md#overlapped-collectives).

        `donate_kernel=True` routes the kernel branch through the donated
        Pallas call: the (m, N) anchor/gradient/multiplier buffers alias
        the outputs and update in place (no extra model-size temp).
        """
        fed = self.fed
        m = fed.num_clients
        m_local = api.local_client_count(m)
        sdt = jnp.dtype(fed.state_dtype)
        sigma = state["sigma"]
        assert stale is None or mask is not None, (
            "stale-x̄ rounds need the engine arrival mask"
        )

        # (1) aggregation — eq. (11) as ONE contiguous model-size mean
        # (under client sharding: the round's single model-size psum).
        # Under a codec the mean is over the decoded uploads. Overlapped
        # rounds instead all-gather the consensus shard reduce-scattered
        # at the END of the previous round — the deferred half of the
        # split collective.
        ef_new = None
        fprev_new = None
        n_scr = None
        hardened = faults is not None or screening is not None
        ovl = state.get("ovl_shard")
        if ovl is not None:
            xbar = api.flat_overlap_consensus(ovl)[0]
        else:
            z_up = state["z"]
            if compressor is not None:
                ef = state.get("ef") if compressor.error_feedback else None
                z_up, ef_new = api.compress_upload(
                    compressor, z_up, ef, spec,
                    key=compress.round_key(state["rng"], state["round"]))
            # faults/screening (core/faults.py): FedGiA's upload is the
            # whole population's z, so the screened mask starts from None
            # (all m rows) and eq. (11) becomes the mean over the rows
            # that arrived finite — same ONE psum, mask/count as riders.
            sc_mask = None
            if hardened:
                z_up, sc_mask, fprev_new, n_scr = api.harden_upload(
                    z_up, None, spec, faults=faults, screening=screening,
                    fault_prev=state.get("fault_prev"),
                    round_idx=state["round"])
            xbar = api.client_mean(z_up, mask=sc_mask,
                                   weights=api.stale_weights(stale))

        # (3) client selection — identical rng stream to the pytree round.
        rng, sel_key = jax.random.split(state["rng"])
        if mask is None:
            sel = api.local_client_slice(
                selection.selection_mask(
                    jax.random.fold_in(sel_key, state["round"]), m, fed.alpha
                )
            )
        else:
            sel = mask

        # (2) per-client gradient — the one boundary that unravels: the
        # loss is a pytree function of the model, everything around it
        # stays flat.
        cast = (
            (lambda t: pt.tree_cast(t, self.model.dtype))
            if self.model is not None and hasattr(self.model, "dtype")
            else (lambda t: t)
        )
        if stale is None or stale.always_fresh:
            if stale is not None:
                xbar_c, stale = api.stale_xbar_view(stale, xbar, sel)
            else:
                xbar_c = broadcast_clients(xbar, m_local)
            losses, grads = self._vg(cast(spec.unravel(xbar)), batch)
        else:
            xbar_c, stale = api.stale_xbar_view(stale, xbar, sel)
            losses, grads = self._vg_per_anchor(
                cast(spec.unravel_stacked(xbar_c)), batch)
        gbar = spec.ravel_stacked(
            pt.tree_cast(pt.tree_scale(grads, 1.0 / m), sdt))  # ḡ_i (m, N)

        # (4) both branches + masked combine, one fused elementwise pass
        if self._use_kernel():
            h = state.get("h")
            if h is None:
                h = jnp.broadcast_to(state["r"], gbar.shape)
            x_new, pi_new, z_new = fedgia_update_flat(
                xbar_c, gbar, state["pi"], h, sel, sigma, m,
                k0=fed.k0, interpret=fed.kernel_interpret,
                donate=donate_kernel,
            )
        else:
            xa, pia, za = self._admm_branch_flat(state, xbar_c, gbar, spec)
            pig = gbar * -1.0  # eq. (16)
            zg = (-1.0 / sigma) * gbar + xbar_c  # eq. (17)
            pi_new = api.masked_update(sel, pia, pig)
            z_new = api.masked_update(sel, za, zg)

        new_state = dict(state)
        new_state.update(
            x=xbar, z=z_new, pi=pi_new, rng=rng, round=state["round"] + 1
        )
        if ef_new is not None:
            new_state["ef"] = ef_new
        if fed.h_policy == "diag_ema":
            new_state["h"] = hparams.update_diag_h(state["h"], gbar,
                                                   state["r"], m)

        if ovl is not None:
            # upload half of the split collective: reduce-scatter the
            # FRESH z (next round's eq. (11) numerator) before handing the
            # round back — the next round's top only all-gathers. The
            # codec key is round_key(rng, round+1): exactly the key the
            # barrier round t+1 would draw, so the compressed stream is
            # unchanged. The g²-norm / loss / selection metrics ride the
            # same collective as scalar psum lanes instead of issuing
            # their own (flat_grad_sq_norm would add a second
            # reduce-scatter).
            z_up_new = z_new
            if compressor is not None:
                ef = state.get("ef") if compressor.error_feedback else None
                z_up_new, ef_new = api.compress_upload(
                    compressor, z_up_new, ef, spec,
                    key=compress.round_key(rng, state["round"] + 1))
                new_state["ef"] = ef_new
            # faults/screening hit the upload where it happens — at the
            # round END. The draw is keyed round+1 (the barrier round
            # whose aggregation this upload feeds, matching the codec
            # key convention), and the screened mask rides the same
            # reduce-scatter's scalar lanes.
            sc_mask = None
            if hardened:
                z_up_new, sc_mask, fprev_new, n_scr = api.harden_upload(
                    z_up_new, None, spec, faults=faults,
                    screening=screening,
                    fault_prev=state.get("fault_prev"),
                    round_idx=state["round"] + 1)
            slot, gsq, f_mean, n_sel = api.flat_overlap_aggregate(
                z_up_new, spec.ravel_stacked(grads), losses, sel, spec,
                mask=sc_mask, weights=api.stale_weights(stale))
            new_state["ovl_shard"] = slot
            metrics = {
                "f_xbar": f_mean,
                "grad_sq_norm": gsq,
                "selected": n_sel,
                "cr": 2.0 * (state["round"] + 1).astype(jnp.float32),
                "local_grad_evals": jnp.float32(1.0),
            }
        else:
            metrics = {
                "f_xbar": api.client_scalar_mean(losses),
                "grad_sq_norm": api.flat_grad_sq_norm(
                    spec.ravel_stacked(grads), spec),
                "selected": api.client_scalar_sum(sel),
                "cr": 2.0 * (state["round"] + 1).astype(jnp.float32),
                "local_grad_evals": jnp.float32(1.0),  # per client per round (C2)
            }
        if fprev_new is not None:
            new_state["fault_prev"] = fprev_new
        if hardened:
            metrics["screened"] = n_scr
        if stale is not None:
            return new_state, stale, metrics
        return new_state, metrics

    # ----------------------------------------------------------------- round
    def round(self, state, batch, mask=None, stale=None):
        """One communication round (Algorithm 1, steps (1)-(5)).

        `mask`: engine participation mask = the ADMM/GD branch split.
        `stale`: async stale-x̄ state (`api.StaleXbar`). When given (mask
        required), each client's gradient and branch anchor is its own
        possibly stale view x̄^(t-s) instead of the fresh x̄ᵗ — the
        inexact-ADMM analysis tolerates the bounded perturbation (see
        docs/async.md). The server-side state update is untouched and
        eq. (11) stays the round's one psum; with a non-uniform
        `stale.weighting` the aggregation downweights each z_i by the age
        of the anchor it was computed against (`api.stale_weights` — the
        incoming `last_used`, i.e. the staleness of the round that
        PRODUCED the current z_i), riding the same psum.
        """
        fed = self.fed
        m = fed.num_clients
        m_local = api.local_client_count(m)
        sdt = jnp.dtype(fed.state_dtype)
        sigma = state["sigma"]
        assert stale is None or mask is not None, (
            "stale-x̄ rounds need the engine arrival mask"
        )

        # (1) aggregation — the round's ONLY model-size communication
        # (under client sharding this is the single psum of the round).
        # Staleness-aware weights (None = uniform = bitwise today's path)
        # downweight z_i computed against old anchors.
        xbar = api.client_mean(state["z"], weights=api.stale_weights(stale))  # eq. (11)

        # (3) client selection. The engine-drawn participation mask (when
        # given) decides the branch split and arrives pre-sliced to this
        # shard's clients; otherwise the in-algorithm §V.B draw derives the
        # full mask from the (replicated) round rng and each shard keeps
        # its own block. The rng splits either way, so the state's rng
        # stream is identical with and without an engine policy.
        rng, sel_key = jax.random.split(state["rng"])
        if mask is None:
            sel = api.local_client_slice(
                selection.selection_mask(
                    jax.random.fold_in(sel_key, state["round"]), m, fed.alpha
                )
            )
        else:
            sel = mask

        # (2) per-client gradient, once per round. Synchronous (and
        # statically-fresh async) rounds evaluate at the shared x̄; stale
        # rounds evaluate at each client's own anchor view.
        cast = (
            (lambda t: pt.tree_cast(t, self.model.dtype))
            if self.model is not None and hasattr(self.model, "dtype")
            else (lambda t: t)
        )
        if stale is None or stale.always_fresh:
            if stale is not None:
                xbar_c, stale = api.stale_xbar_view(stale, xbar, sel)
            else:
                xbar_c = broadcast_clients(xbar, m_local)
            losses, grads = self._vg(cast(xbar), batch)
        else:
            xbar_c, stale = api.stale_xbar_view(stale, xbar, sel)
            losses, grads = self._vg_per_anchor(cast(xbar_c), batch)
        gbar = pt.tree_cast(pt.tree_scale(grads, 1.0 / m), sdt)  # ḡ_i

        # (4) both branches, masked combine
        xa, pia, za = self._admm_branch(state, xbar_c, gbar)
        pig = pt.tree_scale(gbar, -1.0)  # eq. (16)
        zg = pt.tree_axpy(-1.0 / sigma, gbar, xbar_c)  # eq. (17)

        pi_new = api.masked_update(sel, pia, pig)
        z_new = api.masked_update(sel, za, zg)

        new_state = dict(state)
        new_state.update(
            x=xbar, z=z_new, pi=pi_new, rng=rng, round=state["round"] + 1
        )
        if fed.h_policy == "diag_ema":
            new_state["h"] = hparams.update_diag_h(state["h"], gbar, state["r"], m)

        gmean = api.client_mean(grads)
        metrics = {
            "f_xbar": api.client_scalar_mean(losses),
            "grad_sq_norm": pt.tree_sq_norm(gmean),
            "selected": api.client_scalar_sum(sel),
            "cr": 2.0 * (state["round"] + 1).astype(jnp.float32),
            "local_grad_evals": jnp.float32(1.0),  # per client per round (C2)
        }
        if stale is not None:
            return new_state, stale, metrics
        return new_state, metrics

    # ------------------------------------------------------------ diagnostics
    def client_params(self, state):
        """x_i = z_i − π_i/σ (derived; never stored — DESIGN §6 B3)."""
        return pt.tree_axpy(-1.0 / state["sigma"], state["pi"], state["z"])

    def lagrangian(self, state, batch):
        """L(Z^k) of eq. (7) at a round boundary k = t*k0 — the monotone
        quantity of Lemma IV.1. At k in K the anchor is x^{tau_k} =
        mean(z^k) (the aggregation happens FIRST; the lemma's e1 term
        accounts for its decrease), so we evaluate at mean(z), not at the
        previous round's anchor."""
        m = self.fed.num_clients
        sigma = state["sigma"]
        xc = self.client_params(state)
        losses, _ = self._vg_values(xc, batch)
        xbar_c = broadcast_clients(pt.tree_mean_over_axis(state["z"], axis=0), m)
        diff = pt.tree_sub(xc, xbar_c)
        inner = pt.tree_dot(diff, state["pi"])
        quad = 0.5 * sigma * pt.tree_sq_norm(diff)
        return jnp.sum(losses) / m + inner + quad

    def _vg_values(self, xc_stacked, batch):
        loss = jax.vmap(lambda p, b: self.loss_fn(p, b)[0])(xc_stacked, batch)
        return loss, None

    # ----------------------------------------------------- active-set round
    def round_flat_active(self, state, batch, spec, active, stale=None,
                          compressor=None, donate_kernel=False,
                          faults=None, screening=None):
        """Active-store round (``run_rounds(store="active")``).

        FedGiA cannot shrink the round's working set: the GD branch
        (eqs. 15-17) recomputes EVERY non-selected client's (z, pi, h)
        from its fresh local gradient each round, so every client is
        read AND written regardless of the §V.B draw — `active_tile =
        "population"`. Packing m rows into an m-row tile is a pure
        permutation with no memory or compute win, so this delegates to
        the dense masked round (bitwise identical by construction). The
        active store's million-client payoff applies to the frozen-client
        family (FedAvg/FedProx/FedPD/SCAFFOLD), where non-participants
        are genuinely untouched. The same population argument routes the
        codec through the dense upload path (all m rows)."""
        return self.round_flat(state, batch, spec, active.mask, stale,
                               compressor=compressor,
                               donate_kernel=donate_kernel,
                               faults=faults, screening=screening)

    # --------------------------------------------------------------- overlap
    def overlap_finalize(self, state, slot):
        """Engine hook closing an overlapped run: FedGiA already stores the
        FRESH consensus in `state["x"]` every round (x does not lag — the
        carry slot holds the NEXT round's un-gathered numerator), so the
        pending shard is simply dropped."""
        return state
