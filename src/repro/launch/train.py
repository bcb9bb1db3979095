"""Federated training driver.

Runs REAL training (paper examples or transformer archs at reduced scale on
CPU; the same code path drives the production mesh on TPU) with any of the
five federated algorithms.

Rounds are driven by the scan-compiled round engine (core/engine.py):
chunks of rounds compile into one lax.scan with the tolerance check on
device, so the host is not in the per-round loop. `--no-scan` restores the
legacy per-round dispatch for debugging; `--shard-clients N` splits the
client axis over an N-way `data` mesh axis (requires >= N devices);
`--chunk auto` times the candidate chunk lengths on the live run and
keeps the fastest.

Rounds run on the FLAT client-state buffer by default: the model pytree
is raveled once into contiguous (m, N) arrays, eq. (11) is one
model-size reduction and FedGiA's branch update one fused pass (the
batched Pallas kernel on TPU — `--kernel`). `--no-flat` restores the
per-leaf pytree rounds (bitwise-equal single-device, tests/test_flat.py).

`--participation` moves client selection into the engine: a fresh
per-round mask is drawn on device (inside the compiled scan) and fed to
every algorithm — FedGiA uses it as its ADMM/GD branch split, the
baselines freeze masked-out clients (see docs/engine.md).

`--async` turns the participation mask into an ARRIVAL process and runs
staleness-aware overlapped rounds: a straggler works against the x̄ it
last downloaded, at most `--max-staleness` rounds old (see docs/async.md).
`--max-staleness 0` is bitwise identical to the synchronous masked run.

`--store active` swaps the dense (m, N) round working set for a packed
participant tile: each round gathers the selected clients' rows, runs
the local work at O(|C|) instead of O(m), and scatters per-client state
back into the resident buffers. States are bitwise-equal to the dense
store; loss/gradient diagnostics become participant means. This is what
makes m=10^6 clients at alpha=10^-4 tractable (engine_bench `active_1m`).

`--clock` replaces the sampled arrival process with a WALL-CLOCK
simulation (core/clock.py): per-client compute times (`--client-speeds`)
drive event-driven rounds whose arrival mask is derived from simulated
finish times, and the run reports simulated seconds alongside CR.
`--stale-weighting poly|exp` downweights stale contributions in the
aggregation (eq. 11) by decay in anchor age (`--stale-decay`).

`--overlap scatter` splits eq. (11)'s one all-reduce into an early
reduce-scatter of this round's contribution plus a deferred all-gather of
the consensus shard at the TOP of the next round, so the model-size wire
transfer hides behind the next round's local compute (the clock credits
min(compute, comm) per round). `--pod P` spans the sharded client axis
over a compound ("pod", "data") mesh — P pods of `--shard-clients`/P
devices each — bitwise identical to the flat data axis
(docs/engine.md#overlapped-collectives).

`--compression bf16|int8|topk` quantizes/sparsifies the uplink on the
flat comm buffer (core/compress.py, decompress-before-reduce — the round
keeps its ONE model-size all-reduce); `--error-feedback` carries the
per-client codec residual so the error telescopes; `--bandwidth-bps`
makes the clock's comm time BYTE-ACCURATE (the codec's exact wire size
prices each round), so compression shows up as simulated time-to-target,
not just fewer bits (docs/compression.md).

`--faults crash,nan,...` injects client faults into the decoded uplink ON
DEVICE (stateless per-round keys — deterministic everywhere, including
across `--resume`); `--screening` (+ `--clip-norm`) drops non-finite and
clips oversized uploads as a rider on eq. (11)'s ONE collective;
`--quorum` degrades under-quorum rounds to recorded no-ops and
`--deadline-s` closes each simulated round at a wall-clock deadline;
`--watchdog` rolls the state back to the best snapshot after sustained
divergence; `--checkpoint-every N --checkpoint-dir D` snapshots the full
round carry and `--resume` restores it BITWISE (docs/faults.md).

Examples:
  PYTHONPATH=src python -m repro.launch.train --problem linreg --algo fedgia \
      --clients 128 --k0 10 --rounds 200 --tol 1e-7
  PYTHONPATH=src python -m repro.launch.train --problem linreg --algo scaffold \
      --clients 64 --rounds 100 --participation uniform --alpha 0.25
  PYTHONPATH=src python -m repro.launch.train --problem linreg --algo fedgia \
      --clients 64 --rounds 200 --clock constant --client-speeds "$(python -c \
      'print(",".join(str(1+i%4) for i in range(64)))")" \
      --max-staleness 4 --stale-weighting poly
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --reduced \
      --algo fedgia --clients 4 --rounds 20 --seq-len 64 --batch 2
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.config import FedConfig
from repro.configs import get_config, list_architectures
from repro.core import (
    Screening,
    make_algorithm,
    make_clock,
    make_faults,
    make_policy,
    run_rounds,
)
from repro.core.clock import CLOCKS
from repro.core.selection import POLICIES
from repro.data import linreg_noniid, logreg_data
from repro.data.tokens import synthetic_batch_for
from repro.models import (
    LeastSquares,
    LogisticRegression,
    NonConvexLogistic,
    Transformer,
)
from repro.utils import get_logger
from repro.utils.compile_cache import use_compile_cache

log = get_logger("train")


def build_problem(args):
    if args.arch:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        model = Transformer(cfg)
        batch = synthetic_batch_for(
            cfg, args.clients, args.batch, args.seq_len, seed=args.seed
        )
        batch = jax.tree.map(jnp.asarray, batch)
        params0 = model.init(jax.random.PRNGKey(args.seed))
        return model, model.loss, params0, batch
    n = args.dim
    if args.problem == "linreg":
        model = LeastSquares(n)
        raw = linreg_noniid(args.seed, args.samples, n, args.clients)
    elif args.problem == "logreg":
        model = LogisticRegression(n)
        raw = logreg_data(args.seed, args.samples, n, args.clients)
    else:
        model = NonConvexLogistic(n)
        raw = logreg_data(args.seed, args.samples, n, args.clients)
    batch = jax.tree.map(jnp.asarray, raw)
    params0 = model.init(jax.random.PRNGKey(args.seed))
    return model, model.loss, params0, batch


def _parse_csv(value: str, n: int, flag: str, cast):
    try:
        items = [cast(v) for v in value.split(",")]
    except ValueError as e:
        raise SystemExit(f"{flag}: {e}")
    if len(items) != n:
        raise SystemExit(f"{flag} needs {n} values, got {len(items)}")
    return items


def validate_flags(args) -> dict:
    """Cross-flag validation for the engine knobs, shared by `train` and
    testable without building a problem (tests/test_train_flags.py).

    Rejects (SystemExit): `--max-staleness` / `--stale-weighting` without
    `--async` (or `--clock`, which implies it); `--arrival-periods`
    without the periodic policy; `--client-weights` without the weighted
    policy; `--client-speeds` without `--clock`; `--clock` combined with
    an explicit `--participation` (the clock DERIVES the arrival mask);
    `--clock trace` (library-level — needs a duration table); a
    non-positive `--stale-decay` with a decaying weighting; a `--chunk`
    that is neither an int nor "auto"; `--chunk auto` with `--no-scan`
    (the legacy loop has no chunks); `--store active` with `--no-flat`
    (the active store packs the FLAT buffers) or without a participant
    source (`--participation` or `--clock` — there is nothing to pack
    a tile from under legacy full participation); a lossy `--compression`
    with `--no-flat` (codecs run on the flat comm buffer);
    `--error-feedback` without a lossy codec (the identity residual is
    always zero); `--topk-frac` without `--compression topk` or outside
    (0, 1]; `--bandwidth-bps` without `--clock` (byte-accurate comm time
    is a clock feature) or non-positive; `--overlap scatter` with
    `--no-flat` (the carry slot lives on the flat buffers); `--pod`
    without `--shard-clients`, or a `--shard-clients` not divisible by
    `--pod` (each pod holds shard_clients/pod devices); `--store
    offload` with `--shard-clients` (the host/device split is
    single-device), `--overlap scatter` (no carry slot in the
    host-driven loop) or `--chunk auto` (no chunks to tune);
    `--aggregate packed` with `--store dense` (the packed sum needs the
    participant tile); an unknown `--faults` kind, or `--faults` /
    `--screening` with `--no-flat` (both operate on the flat comm
    buffer); `--fault-rate` without `--faults`, with a rate outside
    [0, 1] or a list length that is neither 1 nor len(kinds);
    `--clip-norm` without `--screening`; `--quorum` outside [1, m] or
    without a source of non-arrival (`--participation`, `--clock`,
    `--faults` or `--screening`); `--deadline-s` without `--clock` (the
    deadline cuts SIMULATED rounds) or without `--quorum` (a deadline
    round can close with zero arrivals); `--watchdog-patience` /
    `--watchdog-factor` without `--watchdog`, a patience < 1, a factor
    <= 1, or `--watchdog` with `--store offload` (the snapshot would
    double host residency); `--checkpoint-every` / `--resume` without
    `--checkpoint-dir`, with `--shard-clients`, with `--chunk auto`, or
    with `--no-scan` on a non-offload store (checkpointing rides the
    chunked scan driver / the offload loop).

    Returns the resolved engine knobs: participation kind, clock kind,
    whether async rounds are on (a clock implies them), the parsed
    per-client lists (weights / periods / speeds, or None), the chunk
    size (int or "auto"), whether the flat round path is on, the
    FedConfig kernel knobs resolved from `--kernel`, and the compression
    knobs (codec name or None, error_feedback, topk_frac, bandwidth_bps
    or None).
    """
    kind = getattr(args, "participation", "full")
    clock_kind = getattr(args, "clock", "none")
    async_rounds = getattr(args, "async_rounds", False) or clock_kind != "none"
    chunk = getattr(args, "chunk", "0")
    if chunk != "auto":
        try:
            chunk = int(chunk)
        except ValueError:
            raise SystemExit(
                f"--chunk must be an integer or 'auto', got {chunk!r}")
    elif getattr(args, "no_scan", False):
        raise SystemExit(
            "--chunk auto tunes the scan chunk length and cannot be "
            "combined with --no-scan")
    elif getattr(args, "shard_clients", 0) > 1:
        raise SystemExit(
            "--chunk auto times AOT-precompiled chunks, which the "
            "sharded path does not have — pass a fixed --chunk with "
            "--shard-clients")
    kernel_arg = getattr(args, "kernel", "auto")
    use_kernel = {"auto": None, "on": True, "off": False,
                  "interpret": True}[kernel_arg]
    kernel_interpret = kernel_arg == "interpret"
    if kernel_arg in ("on", "interpret") and getattr(args, "no_flat", False):
        raise SystemExit(
            "--kernel on/interpret requires the flat round path "
            "(drop --no-flat)")
    store = getattr(args, "store", "dense")
    if store in ("active", "offload"):
        if getattr(args, "no_flat", False):
            raise SystemExit(
                f"--store {store} packs the flat (m, N) client buffers and "
                "requires the flat round path (drop --no-flat)")
        if kind == "full" and clock_kind == "none":
            raise SystemExit(
                f"--store {store} needs a per-round participant set to pack "
                "the tile from: pass --participation (uniform/weighted/"
                "cyclic give the fixed-size tile; others bound it by m) "
                "or --clock")
    if store == "offload":
        if getattr(args, "shard_clients", 0) > 1:
            raise SystemExit(
                "--store offload is the single-device host/device split — "
                "under --shard-clients the resident buffers are already "
                "spread over devices; use --store active")
        if getattr(args, "overlap", "off") == "scatter":
            raise SystemExit(
                "--store offload runs the host-driven tile loop — the "
                "overlapped-collective carry slot (--overlap scatter) "
                "does not ride it")
        if chunk == "auto":
            raise SystemExit(
                "--chunk auto tunes the scan chunk length — the "
                "host-driven offload loop (--store offload) has no chunks")
    aggregate = getattr(args, "aggregate", "dense")
    if aggregate == "packed" and store == "dense":
        raise SystemExit(
            "--aggregate packed sums the packed participant tile — it "
            "requires --store active or --store offload")
    if clock_kind != "none" and kind != "full":
        raise SystemExit(
            "--clock derives the arrival mask from simulated finish times "
            "and cannot be combined with --participation"
        )
    if clock_kind == "trace":
        raise SystemExit(
            "--clock trace is library-level (it needs a (T, m) duration "
            "table): build core.clock.TraceClock and pass it to "
            "run_rounds(clock=...) programmatically"
        )
    if (getattr(args, "stale_weighting", "uniform") != "uniform"
            and getattr(args, "stale_decay", 1.0) <= 0):
        raise SystemExit("--stale-decay must be > 0")
    if getattr(args, "max_staleness", 0) and not async_rounds:
        raise SystemExit("--max-staleness requires --async (or --clock)")
    if getattr(args, "stale_weighting", "uniform") != "uniform" and not async_rounds:
        raise SystemExit("--stale-weighting requires --async (or --clock)")
    weights = periods = speeds = None
    weights_arg = getattr(args, "client_weights", "")
    if weights_arg:
        if kind != "weighted":
            raise SystemExit("--client-weights requires --participation weighted")
        weights = _parse_csv(weights_arg, args.clients, "--client-weights", float)
    periods_arg = getattr(args, "arrival_periods", "")
    if periods_arg:
        if kind != "periodic":
            raise SystemExit("--arrival-periods requires --participation periodic")
        periods = _parse_csv(periods_arg, args.clients, "--arrival-periods", int)
    speeds_arg = getattr(args, "client_speeds", "")
    if speeds_arg:
        if clock_kind == "none":
            raise SystemExit("--client-speeds requires --clock")
        speeds = _parse_csv(speeds_arg, args.clients, "--client-speeds", float)
    compression = getattr(args, "compression", "none")
    error_feedback = getattr(args, "error_feedback", False)
    topk_frac = getattr(args, "topk_frac", None)
    bandwidth = getattr(args, "bandwidth_bps", 0.0)
    if compression != "none" and getattr(args, "no_flat", False):
        raise SystemExit(
            "--compression runs on the flat (m, N) comm buffer and "
            "requires the flat round path (drop --no-flat)")
    if error_feedback and compression == "none":
        raise SystemExit(
            "--error-feedback carries the codec residual — it needs a "
            "lossy --compression (bf16/int8/topk)")
    if topk_frac is not None:
        if compression != "topk":
            raise SystemExit("--topk-frac requires --compression topk")
        if not (0.0 < topk_frac <= 1.0):
            raise SystemExit(
                f"--topk-frac must be in (0, 1], got {topk_frac}")
    if bandwidth:
        if bandwidth < 0:
            raise SystemExit(
                f"--bandwidth-bps must be > 0, got {bandwidth}")
        if clock_kind == "none":
            raise SystemExit(
                "--bandwidth-bps prices the wire inside the wall-clock "
                "simulation — it requires --clock")
    overlap = getattr(args, "overlap", "off")
    if overlap == "scatter" and getattr(args, "no_flat", False):
        raise SystemExit(
            "--overlap scatter carries the reduce-scattered consensus "
            "shard on the flat buffers and requires the flat round path "
            "(drop --no-flat)")
    pod = getattr(args, "pod", 0)
    if pod:
        shard = getattr(args, "shard_clients", 0)
        if shard <= 1:
            raise SystemExit(
                "--pod spans the sharded client axis over a (pod, data) "
                "mesh — it requires --shard-clients")
        if shard % pod:
            raise SystemExit(
                f"--shard-clients ({shard}) must be divisible by "
                f"--pod ({pod}): each pod holds shard_clients/pod devices")
    # --- fault-tolerant rounds (docs/faults.md) --------------------------
    fault_kinds = [k for k in getattr(args, "faults", "").split(",") if k]
    if fault_kinds:
        from repro.core.faults import FAULT_KINDS
        bad = sorted(set(fault_kinds) - set(FAULT_KINDS))
        if bad:
            raise SystemExit(
                f"--faults: unknown kind(s) {','.join(bad)} "
                f"(choose from {','.join(FAULT_KINDS)})")
        if getattr(args, "no_flat", False):
            raise SystemExit(
                "--faults corrupts the flat (m, N) comm buffer and "
                "requires the flat round path (drop --no-flat)")
    rate_arg = getattr(args, "fault_rate", "")
    if rate_arg and not fault_kinds:
        raise SystemExit(
            "--fault-rate is the injection probability of --faults — "
            "pass --faults crash,nan,...")
    fault_rates = [0.05]
    if rate_arg:
        try:
            fault_rates = [float(v) for v in rate_arg.split(",")]
        except ValueError as e:
            raise SystemExit(f"--fault-rate: {e}")
        if len(fault_rates) not in (1, len(fault_kinds)):
            raise SystemExit(
                f"--fault-rate needs 1 or {len(fault_kinds)} values, "
                f"got {len(fault_rates)}")
        if any(not 0.0 <= r <= 1.0 for r in fault_rates):
            raise SystemExit(
                f"--fault-rate values must be in [0, 1], got {rate_arg}")
    screening = getattr(args, "screening", False)
    if screening and getattr(args, "no_flat", False):
        raise SystemExit(
            "--screening filters the flat (m, N) comm buffer and "
            "requires the flat round path (drop --no-flat)")
    clip_norm = getattr(args, "clip_norm", 0.0)
    if clip_norm:
        if clip_norm < 0:
            raise SystemExit(f"--clip-norm must be > 0, got {clip_norm}")
        if not screening:
            raise SystemExit(
                "--clip-norm is the screening stage's norm clip — "
                "pass --screening")
    quorum = getattr(args, "quorum", 0)
    if quorum:
        if not 0 < quorum <= args.clients:
            raise SystemExit(
                f"--quorum must be in [1, m={args.clients}], got {quorum}")
        if kind == "full" and clock_kind == "none" and not fault_kinds \
                and not screening:
            raise SystemExit(
                "--quorum needs a source of non-arrival to guard against "
                "— pass --participation, --clock, --faults or --screening")
    deadline_s = getattr(args, "deadline_s", 0.0)
    if deadline_s:
        if deadline_s < 0:
            raise SystemExit(f"--deadline-s must be > 0, got {deadline_s}")
        if clock_kind == "none":
            raise SystemExit(
                "--deadline-s cuts simulated rounds at a wall-clock "
                "deadline — it requires --clock")
        if quorum < 1:
            raise SystemExit(
                "--deadline-s can close rounds with ZERO arrivals — pass "
                "--quorum (>= 1) so they degrade to recorded no-ops "
                "instead of aggregating nothing")
    watchdog = getattr(args, "watchdog", False)
    patience = getattr(args, "watchdog_patience", None)
    factor = getattr(args, "watchdog_factor", None)
    if not watchdog and (patience is not None or factor is not None):
        raise SystemExit(
            "--watchdog-patience/--watchdog-factor tune the divergence "
            "watchdog — pass --watchdog")
    if watchdog:
        patience = 3 if patience is None else patience
        factor = 2.0 if factor is None else factor
        if patience < 1:
            raise SystemExit(
                f"--watchdog-patience must be >= 1, got {patience}")
        if factor <= 1.0:
            raise SystemExit(
                "--watchdog-factor is a divergence threshold RELATIVE to "
                f"the best f̄ seen and must be > 1, got {factor}")
        if store == "offload":
            raise SystemExit(
                "--watchdog keeps a full state snapshot in the carry — "
                "with --store offload that would double the host-resident "
                "buffers; use --store dense/active")
    ckpt_every = getattr(args, "checkpoint_every", 0)
    resume = getattr(args, "resume", False)
    if ckpt_every < 0:
        raise SystemExit(
            f"--checkpoint-every must be >= 0, got {ckpt_every}")
    if ckpt_every or resume:
        if not getattr(args, "checkpoint_dir", ""):
            raise SystemExit(
                "--checkpoint-every/--resume need --checkpoint-dir to "
                "write/read the round-carry snapshots")
        if getattr(args, "shard_clients", 0) > 1:
            raise SystemExit(
                "checkpointing round-trips the carry through host npz — "
                "it runs unsharded (drop --shard-clients)")
        if chunk == "auto":
            raise SystemExit(
                "--chunk auto re-times candidate chunk lengths — "
                "checkpoint boundaries need a fixed --chunk")
        if getattr(args, "no_scan", False) and store != "offload":
            raise SystemExit(
                "--checkpoint-every/--resume ride the chunked scan "
                "driver (or the offload loop) — drop --no-scan")
    return {
        "kind": kind,
        "clock_kind": clock_kind,
        "async_rounds": async_rounds,
        "weights": weights,
        "periods": periods,
        "speeds": speeds,
        "chunk": chunk,
        "flat": not getattr(args, "no_flat", False),
        "store": store,
        "aggregate": aggregate,
        "use_kernel": use_kernel,
        "kernel_interpret": kernel_interpret,
        "compression": None if compression == "none" else compression,
        "error_feedback": error_feedback,
        "topk_frac": 0.1 if topk_frac is None else topk_frac,
        "bandwidth_bps": bandwidth if bandwidth else None,
        "overlap": overlap,
        "pod": pod,
        "fault_kinds": fault_kinds,
        "fault_rates": fault_rates,
        "screening": screening,
        "clip_norm": clip_norm if clip_norm else None,
        "quorum": quorum,
        "deadline_s": deadline_s if deadline_s else None,
        "watchdog": watchdog,
        "watchdog_patience": 3 if patience is None else patience,
        "watchdog_factor": 2.0 if factor is None else factor,
        "checkpoint_every": ckpt_every,
        "resume": resume,
    }


def build_algorithm(args, parsed: dict):
    """The algorithm, initial state and batch that `train(args)` runs;
    `parsed` is `validate_flags(args)`."""
    model, loss_fn, params0, batch = build_problem(args)
    fed = FedConfig(
        algorithm=args.algo,
        num_clients=args.clients,
        k0=args.k0,
        alpha=args.alpha,
        sigma_t=args.sigma_t,
        h_policy=args.h_policy,
        collapsed=not args.unrolled,
        lr=args.lr,
        auto_lipschitz=args.arch is not None,
        use_kernel=parsed["use_kernel"],
        kernel_interpret=parsed["kernel_interpret"],
    )
    algo = make_algorithm(fed, loss_fn, model=model)
    state = algo.init(params0, jax.random.PRNGKey(args.seed + 1), init_batch=batch)
    return algo, state, batch


def train(args) -> dict:
    parsed = validate_flags(args)
    algo, state, batch = build_algorithm(args, parsed)

    # engine knobs default off so programmatic callers can pass a bare
    # Namespace with only the legacy fields
    shard_clients = getattr(args, "shard_clients", 0)
    mesh = None
    client_axis = "data"
    if shard_clients > 1:
        from repro.launch.mesh import make_host_mesh

        if parsed["pod"]:
            mesh = make_host_mesh(pod=parsed["pod"],
                                  data=shard_clients // parsed["pod"])
            client_axis = ("pod", "data")
            log.info("pod-spanning client axis: %d pods x %d devices",
                     parsed["pod"], shard_clients // parsed["pod"])
        else:
            mesh = make_host_mesh(data=shard_clients)
    if parsed["overlap"] == "scatter":
        log.info("overlapped collectives: eq. (11) split into an early "
                 "reduce-scatter + a deferred consensus all-gather")

    # engine-level participation (core/selection.py): "full" -> None keeps
    # the legacy in-algorithm behaviour (FedGiA's internal §V.B draw)
    kind = parsed["kind"]
    policy = make_policy(
        kind,
        args.clients,
        args.alpha,
        seed=args.seed,
        weights=parsed["weights"],
        drop_prob=getattr(args, "drop_prob", 0.2),
        horizon=max(args.rounds, 1),
        periods=parsed["periods"],
    )
    if policy is not None:
        if kind in ("straggler", "periodic"):
            log.info("participation: %s policy (per-round varying |C|), m=%d",
                     kind, args.clients)
        else:
            log.info("participation: %s policy, alpha=%.2f (|C|=%d of m=%d)",
                     kind, args.alpha, policy.n_selected, args.clients)

    # wall-clock simulation (core/clock.py): the clock derives the arrival
    # mask from simulated finish times and implies async rounds
    clock = make_clock(
        parsed["clock_kind"],
        args.clients,
        compute_s=parsed["speeds"],
        sigma=getattr(args, "clock_sigma", 0.5),
        seed=args.seed,
        bandwidth_bps=parsed["bandwidth_bps"],
        deadline_s=parsed["deadline_s"],
    )
    # fault-tolerant rounds (core/faults.py, docs/faults.md)
    faults = make_faults(
        parsed["fault_kinds"], parsed["fault_rates"],
        num_clients=args.clients, seed=args.seed,
        scale=getattr(args, "fault_scale", 1e6),
    )
    screening = (Screening(clip_norm=parsed["clip_norm"])
                 if parsed["screening"] else None)
    if faults is not None:
        log.info("fault injection: %s at rate(s) %s (on-device, "
                 "stateless per-round keys)",
                 ",".join(parsed["fault_kinds"]),
                 ",".join("%g" % r for r in parsed["fault_rates"]))
    if screening is not None:
        log.info("upload screening: finite check%s riding eq. (11)'s "
                 "collective",
                 (" + norm clip at %g" % parsed["clip_norm"])
                 if parsed["clip_norm"] else "")
    if parsed["quorum"]:
        log.info("quorum: rounds with < %d accepted uploads degrade to "
                 "recorded no-ops", parsed["quorum"])
    if parsed["deadline_s"] is not None:
        log.info("round deadline: %.3g simulated seconds (late clients "
                 "re-arrive next round)", parsed["deadline_s"])
    if parsed["watchdog"]:
        log.info("divergence watchdog: rollback after %d rounds above "
                 "%.2gx the best f̄", parsed["watchdog_patience"],
                 parsed["watchdog_factor"])
    if parsed["checkpoint_every"]:
        log.info("checkpointing the round carry every %d rounds to %s%s",
                 parsed["checkpoint_every"], args.checkpoint_dir,
                 " (resuming)" if parsed["resume"] else "")
    if parsed["compression"] is not None:
        log.info("uplink compression: %s codec%s%s", parsed["compression"],
                 " + error feedback" if parsed["error_feedback"] else "",
                 (" (frac=%.2f)" % parsed["topk_frac"])
                 if parsed["compression"] == "topk" else "")
    if parsed["bandwidth_bps"] is not None:
        log.info("byte-accurate comm clock: %.3g bytes/s per client",
                 parsed["bandwidth_bps"])
    async_rounds = parsed["async_rounds"]
    max_staleness = getattr(args, "max_staleness", 0)
    stale_weighting = getattr(args, "stale_weighting", "uniform")
    if async_rounds:
        if policy is None and clock is None:
            raise SystemExit(
                "--async needs an arrival process: pass --participation "
                "straggler/periodic/... (the mask is who communicates) "
                "or --clock (event-driven wall-clock arrivals)"
            )
        log.info("async rounds: stale-x̄ engine, max_staleness=%d, "
                 "weighting=%s", max_staleness, stale_weighting)
    if clock is not None:
        log.info("wall-clock rounds: %s clock, m=%d", clock.name, args.clients)
    if parsed["store"] == "active":
        cap = args.clients if clock is not None else policy.active_capacity
        log.info("active-set store: (%d, N) participant tile gathered/"
                 "scattered per round (m=%d resident)", cap, args.clients)
    elif parsed["store"] == "offload":
        cap = args.clients if clock is not None else policy.active_capacity
        log.info("host-offloaded store: resident client buffers in host "
                 "memory, (%d, N) tiles shuttled per round (m=%d)",
                 cap, args.clients)
    if parsed["aggregate"] == "packed":
        log.info("packed aggregation: eq. (11) sums the participant tile "
                 "directly (fp tolerance vs the bitwise dense layout)")

    res = run_rounds(
        algo, state, batch, args.rounds,
        tol=args.tol, scan=not getattr(args, "no_scan", False),
        chunk_size=parsed["chunk"], mesh=mesh,
        participation=policy, clock=clock,
        async_rounds=async_rounds, max_staleness=max_staleness,
        stale_weighting=stale_weighting,
        stale_decay=getattr(args, "stale_decay", 1.0),
        flat=parsed["flat"],
        store=parsed["store"],
        aggregate=parsed["aggregate"],
        compression=parsed["compression"],
        error_feedback=parsed["error_feedback"],
        topk_frac=parsed["topk_frac"],
        overlap=parsed["overlap"],
        client_axis=client_axis,
        faults=faults,
        screening=screening,
        quorum=parsed["quorum"],
        watchdog=parsed["watchdog"],
        watchdog_patience=parsed["watchdog_patience"],
        watchdog_factor=parsed["watchdog_factor"],
        checkpoint_every=parsed["checkpoint_every"],
        checkpoint_dir=(args.checkpoint_dir or None)
        if (parsed["checkpoint_every"] or parsed["resume"]) else None,
        resume=parsed["resume"],
    )
    history = [
        {"round": r, "f": float(res.history["f_xbar"][r]),
         "err": float(res.history["grad_sq_norm"][r])}
        for r in range(res.rounds_run)
    ]
    for h in history:
        if h["round"] % args.log_every == 0 or h["round"] == res.rounds_run - 1:
            log.info("round %4d  f=%.6f  |grad|^2=%.3e",
                     h["round"], h["f"], h["err"])
    if res.stopped_early:
        log.info("tolerance reached at round %d", res.rounds_run - 1)
    result = {
        "algo": args.algo,
        "participation": kind,  # the CLI kind, reusable as --participation
        "rounds": res.rounds_run,
        "cr": 2 * res.rounds_run,
        "final_f": history[-1]["f"],
        "final_err": history[-1]["err"],
        "wall_s": res.wall_s,
        "history": history,
    }
    if async_rounds:
        result["max_staleness"] = max_staleness
        result["stale_weighting"] = stale_weighting
        result["staleness_max_seen"] = int(res.history["staleness_max"].max())
        log.info("async: max staleness actually used = %d (bound %d)",
                 result["staleness_max_seen"], max_staleness)
    if parsed["compression"] is not None:
        result["compression"] = parsed["compression"]
        result["error_feedback"] = parsed["error_feedback"]
    if clock is not None:
        result["clock"] = clock.name
        result["sim_time_s"] = float(res.history["sim_time"][-1])
        log.info("simulated wall-clock: %.3f s to round %d "
                 "(time-to-target when the tolerance stopped the run)",
                 result["sim_time_s"], res.rounds_run - 1)
        if parsed["bandwidth_bps"] is not None:
            result["bytes_up"] = float(res.history["bytes_up"].sum())
            result["bytes_down"] = float(res.history["bytes_down"].sum())
            log.info("wire totals: %.0f B up / %.0f B down over %d rounds",
                     result["bytes_up"], result["bytes_down"],
                     res.rounds_run)
    if "screened" in res.history:
        result["screened_min"] = int(res.history["screened"].min())
    if "degraded" in res.history:
        result["degraded_rounds"] = int(res.history["degraded"].sum())
        if result["degraded_rounds"]:
            log.info("%d round(s) missed the quorum and degraded to "
                     "no-ops", result["degraded_rounds"])
    if "rollback" in res.history:
        result["rollbacks"] = int(res.history["rollback"].sum())
        if result["rollbacks"]:
            log.info("watchdog rolled the state back %d time(s)",
                     result["rollbacks"])
    if args.checkpoint_dir and not (parsed["checkpoint_every"]
                                    or parsed["resume"]):
        # legacy final-state save; when the engine owns the directory
        # (--checkpoint-every/--resume) it already persisted the full
        # round carry there and a state-only file would shadow it
        save_checkpoint(args.checkpoint_dir, res.rounds_run, res.state,
                        extra={"algo": args.algo})
        log.info("checkpoint written to %s", args.checkpoint_dir)
    log.info(
        "done: %d rounds (CR=%d) in %.2fs  f=%.6f err=%.2e",
        result["rounds"], result["cr"], res.wall_s, result["final_f"],
        result["final_err"],
    )
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="linreg",
                    choices=["linreg", "logreg", "ncvx_logreg"])
    ap.add_argument("--arch", choices=list_architectures())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--algo", default="fedgia",
                    choices=["fedgia", "fedavg", "fedprox", "fedpd", "scaffold"])
    ap.add_argument("--clients", type=int, default=128)
    ap.add_argument("--k0", type=int, default=5)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--sigma-t", type=float, default=0.15)
    ap.add_argument("--h-policy", default="scalar",
                    choices=["scalar", "diag_ema", "gram"])
    ap.add_argument("--unrolled", action="store_true")
    ap.add_argument("--no-scan", action="store_true",
                    help="legacy per-round dispatch loop (debugging)")
    ap.add_argument("--chunk", default="0",
                    help="rounds per compiled scan chunk (0 = default "
                         "sizing), or 'auto' to time the candidate chunk "
                         "lengths (8/32/128) on the live run and keep the "
                         "fastest — deterministic results under --tol 0")
    ap.add_argument("--no-flat", action="store_true",
                    help="disable the flat-buffer round path (ravel-once "
                         "(m, N) client state, contiguous eq.-11 "
                         "all-reduce, batched round kernel) and run the "
                         "per-leaf pytree rounds; both paths are bitwise-"
                         "equal on a single device (tests/test_flat.py)")
    ap.add_argument("--kernel", default="auto",
                    choices=["auto", "on", "off", "interpret"],
                    help="route FedGiA's flat collapsed round through the "
                         "batched Pallas fedgia_update kernel: auto "
                         "(kernel on TPU, fused jnp elsewhere), on, off, "
                         "or interpret (Pallas interpret mode — CPU "
                         "validation). Requires the flat path")
    ap.add_argument("--store", default="dense",
                    choices=["dense", "active", "offload"],
                    help="client-state execution strategy for the flat "
                         "path: dense (default, every round's working set "
                         "is (m, N) with non-participants masked out), "
                         "active (each round gathers the participants "
                         "into a packed (capacity, N) tile, runs local "
                         "work at O(capacity) instead of O(m), and "
                         "scatters per-client state back — states bitwise-"
                         "equal to dense, loss/grad diagnostics become "
                         "participant means; the million-client regime, "
                         "see docs/engine.md#active-set-client-store), or "
                         "offload (the active tile loop with the resident "
                         "(m, N) client buffers + batch + stale anchor in "
                         "HOST memory — m bounded by host RAM, bitwise "
                         "equal to active; single device only, see "
                         "docs/engine.md#host-offloaded-store and "
                         "docs/scaling.md). Requires --participation or "
                         "--clock; rejected with --no-flat")
    ap.add_argument("--aggregate", default="dense",
                    choices=["dense", "packed"],
                    help="eq.-(11) aggregation layout for the active/"
                         "offload stores: dense (default — scatter the "
                         "participant tile back to the (m, N) layout "
                         "before reducing, bitwise the dense store) or "
                         "packed (sum the (capacity, N) tile directly — "
                         "O(capacity*N), no dense aggregation temp, ~1 ulp "
                         "fp tolerance; docs/engine.md#packed-aggregation)")
    ap.add_argument("--shard-clients", type=int, default=0,
                    help="shard the client axis over an N-way data mesh")
    ap.add_argument("--pod", type=int, default=0,
                    help="span the sharded client axis over a compound "
                         "(pod, data) mesh: --pod P builds P pods of "
                         "--shard-clients/P devices each and the round's "
                         "collectives run over both axes — bitwise "
                         "identical to the flat data axis. Requires "
                         "--shard-clients divisible by P")
    ap.add_argument("--overlap", default="off", choices=["off", "scatter"],
                    help="overlapped eq.-(11) collectives: off (default — "
                         "the round's one model-size all-reduce, bitwise "
                         "the PR-5 program) or scatter (reduce-scatter "
                         "the round's contribution early, all-gather the "
                         "consensus shard at the top of the NEXT round, so "
                         "the model-size wire hides behind local compute; "
                         "the wall clock credits min(compute, comm) per "
                         "round — docs/engine.md#overlapped-collectives). "
                         "Requires the flat path")
    ap.add_argument("--participation", default="full", choices=POLICIES,
                    help="engine-level per-round client participation: "
                         "full (legacy in-algorithm behaviour), uniform "
                         "(paper §V.B alpha-sampling), weighted "
                         "(sampling weighted by --client-weights), cyclic "
                         "(round-robin blocks), straggler (iid "
                         "availability dropout), periodic (deterministic "
                         "heterogeneous arrival speeds)")
    ap.add_argument("--client-weights", default="",
                    help="comma-separated per-client sampling weights "
                         "(e.g. local data sizes) for --participation "
                         "weighted; default: equal weights")
    ap.add_argument("--drop-prob", type=float, default=0.2,
                    help="per-round client dropout prob (straggler policy)")
    ap.add_argument("--arrival-periods", default="",
                    help="comma-separated per-client arrival periods for "
                         "--participation periodic (client i communicates "
                         "every p_i rounds); default: speeds cycling 1..4")
    ap.add_argument("--async", dest="async_rounds", action="store_true",
                    help="staleness-aware overlapped rounds: the "
                         "participation mask becomes the arrival process "
                         "and stragglers work against their last-"
                         "downloaded x̄ (docs/async.md)")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="bound on the stale-x̄ age in rounds (--async); "
                         "0 = bitwise-identical to the synchronous run")
    ap.add_argument("--clock", default="none", choices=("none",) + CLOCKS,
                    help="wall-clock simulation (implies --async): derive "
                         "the arrival mask from per-client compute times "
                         "instead of a sampled policy — constant "
                         "(fixed per-client speeds), lognormal (jittered), "
                         "trace (library-level; needs a duration table). "
                         "Reports simulated seconds alongside CR")
    ap.add_argument("--client-speeds", default="",
                    help="comma-separated per-client compute seconds for "
                         "--clock (default: speeds cycling 1..4, the "
                         "wall-clock twin of the periodic policy)")
    ap.add_argument("--clock-sigma", type=float, default=0.5,
                    help="lognormal compute-time jitter for --clock "
                         "lognormal")
    ap.add_argument("--stale-weighting", default="uniform",
                    choices=["uniform", "poly", "exp"],
                    help="staleness-aware aggregation (--async/--clock): "
                         "downweight a contribution computed against an "
                         "s-rounds-old anchor — uniform (unweighted, "
                         "bitwise today's path), poly ((1+s)^-decay), "
                         "exp (e^(-decay*s))")
    ap.add_argument("--stale-decay", type=float, default=1.0,
                    help="decay rate for --stale-weighting poly/exp")
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8", "topk"],
                    help="uplink codec on the flat comm buffer "
                         "(core/compress.py): none (bitwise identity — "
                         "the uncompressed engine), bf16 (2 B/lane), int8 "
                         "(per-client affine, stochastic rounding, ~1 "
                         "B/lane), topk (keep the --topk-frac largest-|.| "
                         "lanes). Decompress-before-reduce: the round "
                         "keeps its one model-size all-reduce. Requires "
                         "the flat path")
    ap.add_argument("--topk-frac", type=float, default=None,
                    help="fraction of lanes kept by --compression topk "
                         "(default 0.1)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry per-client error-feedback residuals (one "
                         "extra (m, N) flat buffer in the scan carry): "
                         "each upload adds the previous rounds' codec "
                         "error back in, so the compression error "
                         "telescopes instead of accumulating. Requires a "
                         "lossy --compression")
    ap.add_argument("--bandwidth-bps", type=float, default=0.0,
                    help="per-client uplink/downlink bandwidth in bytes/s "
                         "for --clock: comm time becomes BYTE-ACCURATE "
                         "(the codec's exact wire size per round, "
                         "core/compress.py) and the run reports "
                         "bytes_up/bytes_down; 0 keeps the constant "
                         "comm-time model bitwise")
    ap.add_argument("--faults", default="",
                    help="comma-separated fault kinds injected into the "
                         "decoded uplink ON DEVICE each round "
                         "(core/faults.py, drawn from stateless per-round "
                         "keys — deterministic across scan/legacy, stores, "
                         "shardings and checkpoint resume): crash (drop "
                         "the upload), nan / inf (corrupt a prefix of the "
                         "row), explode (scale the update by "
                         "--fault-scale), replay (re-send the previous "
                         "round's upload). Requires the flat path")
    ap.add_argument("--fault-rate", default="",
                    help="per-client per-round injection probability for "
                         "--faults: one value broadcast over all kinds, "
                         "or one per kind (comma-separated); default 0.05")
    ap.add_argument("--fault-scale", type=float, default=1e6,
                    help="magnitude multiplier for the explode fault")
    ap.add_argument("--screening", action="store_true",
                    help="defensive server-side screening of the decoded "
                         "uploads (api.harden_upload): rows with any "
                         "non-finite entry are dropped from the "
                         "aggregation mask before eq. (11)'s reduction — "
                         "the round keeps its ONE model-size all-reduce "
                         "(the finite check rides the same collective). "
                         "Useful without --faults too (real NaN guards)")
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="screening norm clip: finite rows with l2 norm "
                         "above this are scaled onto the clip ball "
                         "(defuses explode faults). Requires --screening")
    ap.add_argument("--quorum", type=int, default=0,
                    help="minimum accepted-upload count for a round to "
                         "commit: an under-quorum round becomes a recorded "
                         "no-op (x̄ carried, history row flagged "
                         "degraded=1). Requires a source of non-arrival "
                         "(--participation, --clock, --faults or "
                         "--screening); required >= 1 with --deadline-s")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="wall-clock round deadline for --clock: each "
                         "round closes after this many simulated seconds "
                         "and only clients that finished participate "
                         "(late clients re-arrive next round). Zero-"
                         "arrival rounds degrade under --quorum")
    ap.add_argument("--watchdog", action="store_true",
                    help="divergence watchdog: track the best f̄ seen and "
                         "a state snapshot in the carry; after "
                         "--watchdog-patience consecutive rounds with "
                         "f̄ > --watchdog-factor x best (NaN counts as "
                         "diverged) restore the snapshot and flag "
                         "rollback=1 in the history. Doubles the carry "
                         "state; not available with --store offload")
    ap.add_argument("--watchdog-patience", type=int, default=None,
                    help="diverged rounds tolerated before the rollback "
                         "(default 3). Requires --watchdog")
    ap.add_argument("--watchdog-factor", type=float, default=None,
                    help="divergence threshold relative to the best f̄ "
                         "seen (default 2.0, must be > 1). Requires "
                         "--watchdog")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot the FULL round carry (client buffers, "
                         "policy/clock/staleness/watchdog state, rng, "
                         "metric history) to --checkpoint-dir every this "
                         "many rounds (atomic npz, checkpoint/); a "
                         "--resume run restores the newest snapshot and "
                         "is BITWISE identical to the uninterrupted run. "
                         "Scan and offload paths; needs a fixed --chunk "
                         "and no --shard-clients")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint under "
                         "--checkpoint-dir (fresh start when none "
                         "exists); the run config must hash-match the "
                         "checkpointing run")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--tol", type=float, default=1e-7)
    ap.add_argument("--dim", type=int, default=100)
    ap.add_argument("--samples", type=int, default=12800)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default="")
    return ap


def main():
    use_compile_cache()
    train(build_parser().parse_args())


if __name__ == "__main__":
    main()
