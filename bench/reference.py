"""Plain reference of Alg. 1 (energy-harvesting FL with VAoI scheduling).

Written from the paper's description and the configuration file alone; it
imports nothing of the program.  Python loops over epochs, slots, clients
and SGD steps; numpy for the integer slot dynamics; the CNN in plain
``jax.numpy`` at ``highest`` precision in float32 (the probe forward takes
every client's probe images through one pass of the model's batch axis).
No vmap, no scan, no compaction, no kernels.  The random draws follow the same PRNG recipe as the
simulator (the key split per epoch, Bernoulli arrivals per slot, top-k with
uniform tie-break noise, one permutation per client), so that a correct
simulator and this reference make the same decisions wherever precision does
not decide them.

``numerics=CONTROL`` runs the same reference in bfloat16 at default
precision: the lower precision a later change might be tempted to use.  The
benchmark's own runs never run it; ``bench/calibrate.py`` and the tests do.

Lockstep.  The one float-valued decision of Alg. 1 is VAoI's ``M_i >= mu``
(Eq. 7).  Where the reference's ``M_i`` lies within ``margin`` of ``mu``, the
program's default-precision convolutions may decide it the other way, and
the two trajectories part for good.  Given the program's per-epoch age sums
(``witness``), the reference settles such a decision the program's way when
the sum pins it down (all ambiguous clients decided alike); when it cannot,
it ends the lockstep there (``t_stop``) and goes on with its own decisions.
Under VAoI the lockstep also ends at the first epoch after any client
trained: from then on ``M_i`` reads models and moments that the program
trained at default precision, which move it by far more than any margin
that would leave the lockstep meaningful (PERF.md).  Epochs before
``t_stop`` are compared exactly (``bench/compare.py``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Numerics(NamedTuple):
    dtype: Any
    precision: Any


REFERENCE = Numerics(jnp.float32, jax.lax.Precision.HIGHEST)
CONTROL = Numerics(jnp.bfloat16, jax.lax.Precision.DEFAULT)


# --------------------------------------------------------------------------
# The client model: 6 conv (3x3, SAME, ReLU; 2x2 max-pool after every second),
# then fully connected layers, softmax output (paper §V).
# --------------------------------------------------------------------------


def init_params(model: dict, key: jax.Array) -> Dict[str, jax.Array]:
    convs, fcs = model["conv_channels"], model["fc_dims"]
    ks = jax.random.split(key, len(convs) + len(fcs) + 1)
    p, cin = {}, model["in_channels"]
    for i, cout in enumerate(convs):
        p[f"conv{i}_w"] = jax.random.normal(ks[i], (3, 3, cin, cout), jnp.float32) * (
            1.0 / jnp.sqrt(9 * cin)
        )
        p[f"conv{i}_b"] = jnp.zeros((cout,), jnp.float32)
        cin = cout
    side = model["image_size"] // 8
    dims = [side * side * convs[-1], *fcs, model["num_classes"]]
    for i in range(len(dims) - 1):
        w = jax.random.normal(ks[len(convs) + i], (dims[i], dims[i + 1]), jnp.float32)
        p[f"fc{i}_w"] = w / jnp.sqrt(dims[i])
        p[f"fc{i}_b"] = jnp.zeros((dims[i + 1],), jnp.float32)
    return p


def logits(model: dict, num: Numerics, p: dict, x: jax.Array) -> jax.Array:
    x = x.astype(num.dtype)
    for i in range(len(model["conv_channels"])):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}_w"].astype(num.dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=num.precision,
        )
        x = jnp.maximum(x + p[f"conv{i}_b"].astype(num.dtype), 0)
        if i % 2 == 1:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    x = x.reshape(x.shape[0], -1)
    n_fc = len(model["fc_dims"]) + 1
    for i in range(n_fc):
        x = jnp.dot(x, p[f"fc{i}_w"].astype(num.dtype), precision=num.precision)
        x = x + p[f"fc{i}_b"].astype(num.dtype)
        if i < n_fc - 1:
            x = jnp.maximum(x, 0)
    return x


def _softmax_mean(z: jax.Array) -> jax.Array:
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    return jnp.mean(e / jnp.sum(e, axis=-1, keepdims=True), axis=0)


def _loss(model, num, p, x, y):
    z = logits(model, num, p, x)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


class Model:
    """Jitted pieces of the reference model."""

    def __init__(self, model: dict, num: Numerics, lr: float):
        self.predict = jax.jit(lambda p, x: jnp.argmax(logits(model, num, p, x), axis=-1))

        def probe(p, x):
            # every client's probe batch through one forward pass (the batch
            # axis of the model), then each client's mean softmax
            n, b = x.shape[:2]
            z = logits(model, num, p, x.reshape((n * b,) + x.shape[2:]))
            z = z - jnp.max(z, axis=-1, keepdims=True)
            e = jnp.exp(z)
            return jnp.mean((e / jnp.sum(e, axis=-1, keepdims=True)).reshape(n, b, -1), axis=1)

        self.probe = jax.jit(probe)

        def sgd_step(p, fsum, xs, ys, step):
            x, y = xs[step], ys[step]
            g = jax.grad(lambda q: _loss(model, num, q, x, y))(p)
            p = {k: (p[k] - jnp.asarray(lr, num.dtype) * g[k]).astype(num.dtype) for k in p}
            f = _softmax_mean(logits(model, num, p, x)).astype(jnp.float32)
            return p, fsum + f * x.shape[0]

        self.sgd_step = jax.jit(sgd_step)


# --------------------------------------------------------------------------
# Random draws: the simulator's PRNG recipe, one jitted call per epoch.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "slots", "p_bc"))
def _epoch_draws(key, *, n, slots, p_bc):
    """Tie-break noise, the (slots, n) energy arrivals, per-client training
    keys and the next epoch's key."""
    k_sel, k_scan, k_train, k_next = jax.random.split(key, 4)
    noise = jax.random.uniform(k_sel, (n,), minval=0.0, maxval=1e-3)
    charges, hk = [], k_scan
    for _ in range(slots):
        k1, hk = jax.random.split(hk)
        charges.append(jax.random.bernoulli(k1, p_bc, (n,)))
    return noise, jnp.stack(charges), jax.random.split(k_train, n), k_next


def macro_f1(preds: np.ndarray, labels: np.ndarray, num_classes: int) -> float:
    f1 = []
    for c in range(num_classes):
        tp = np.sum((preds == c) & (labels == c))
        fp = np.sum((preds == c) & (labels != c))
        fn = np.sum((preds != c) & (labels == c))
        f1.append(2 * tp / max(2 * tp + fp + fn, 1))
    return float(np.mean(f1))


# The scenarios the reference implements: Bernoulli(p_bc) energy arrivals,
# a static data stream, an uplink that delivers every upload.
SUPPORTED = {"harvest": "bernoulli", "stream": "static", "channel": "ideal"}


def check_supported(settings: dict) -> None:
    """Raise unless ``settings`` (a configuration's ``sim`` block, with any
    scenario keys a traffic file sets) asks only for scenarios this
    reference implements, with no extra parameters."""
    for key, plain in SUPPORTED.items():
        if settings.get(key, plain) != plain or settings.get(f"{key}_params"):
            raise ValueError(
                f"the reference implements {key}={plain!r} only, not "
                f"{settings.get(key)!r} with {settings.get(f'{key}_params') or {}}"
            )


@jax.jit
def _mean_of(msgs: List[dict]) -> dict:
    """FedAvg: the plain mean of the uploaded messages, leaf by leaf."""
    out = {}
    for k in msgs[0]:
        acc = msgs[0][k]
        for m in msgs[1:]:
            acc = acc + m[k]
        out[k] = acc / jnp.asarray(len(msgs), acc.dtype)
    return out


def simulate(
    cfg: dict,
    model: dict,
    data: Dict[str, Any],
    seed: int,
    epochs: int,
    policy: str,
    *,
    numerics: Numerics = REFERENCE,
    witness: Sequence[int] | None = None,
    margin: float = 0.0,
    m_log: List[np.ndarray] | None = None,
) -> Dict[str, Any]:
    """Run Alg. 1 for ``epochs`` epochs from ``seed``.

    ``cfg`` holds the simulator settings of the configuration file (N, S,
    kappa, p_bc, k, mu, e_max, probe_size, lr, eval_every); ``policy`` is
    ``"vaoi"`` (Alg. 2 top-k by VAoI) or ``"fedavg"`` (every client, as soon
    as it has the energy).  ``witness``: the program's per-epoch sum of
    ages, for the lockstep (module docstring).  ``m_log``, if given,
    receives each lockstep epoch's per-client distances.

    Returns per-epoch metrics, the F1 after every ``eval_every`` epochs, the
    initial and final global model, every client's last message, the final
    per-client integer state, the last aggregation's uploaders
    (``last_uploads``), and the lockstep's ``t_stop`` (``epochs`` when it held to the end),
    ``witness_faults`` (epochs whose age sum no decision of the ambiguous
    clients can give) and ``min_margin`` (the smallest ``|M_i - mu|`` of an
    unselected client over the lockstep)."""
    if policy not in ("vaoi", "fedavg"):
        raise ValueError(f"reference implements vaoi and fedavg, not {policy!r}")
    check_supported(cfg)
    N, S, kappa = cfg["num_clients"], cfg["slots_per_epoch"], cfg["kappa"]
    k, mu, e_max = cfg["k"], cfg["mu"], cfg["e_max"]
    probe, eval_every = cfg["probe_size"], cfg["eval_every"]
    dt = numerics.dtype
    net = Model(model, numerics, cfg["lr"])

    images, labels = data["images"], data["labels"]
    pool = images.shape[1]
    bs = max(1, pool // kappa)
    test_images = data["test_images"]
    test_labels = np.asarray(data["test_labels"])

    key = jax.random.PRNGKey(seed)
    k_init, key = jax.random.split(key)
    init = init_params(model, k_init)
    glob = {n: v.astype(dt) for n, v in init.items()}
    msgs: List[dict] = [glob] * N
    # who uploaded in the last epoch that had uploads, while none of them
    # has trained since
    last_ups: List[int] = []
    h = [np.zeros(model["num_classes"], np.float64) for _ in range(N)]
    age = np.zeros(N, np.int64)
    battery = np.zeros(N, np.int64)
    pending = np.zeros(N, bool)

    rows = {k_: [] for k_ in ("energy", "avg_age", "age_sum", "n_started", "n_uploaded", "avg_m")}
    f1s: List[float] = []
    t_stop, witness_faults, min_margin = epochs, 0, np.inf
    trained = False
    uses_vaoi = policy == "vaoi"

    for t in range(epochs):
        noise, charges, train_keys, key = _epoch_draws(key, n=N, slots=S, p_bc=float(cfg["p_bc"]))
        noise, charges = np.asarray(noise), np.asarray(charges)

        # --- Alg. 2: who is scheduled this epoch ---
        if uses_vaoi:
            a32 = age.astype(np.float32)
            total = np.float32(a32.sum())
            p = a32 / total if total > 0 else np.zeros(N, np.float32)
            scores = (p + noise).astype(np.float32)
            order = sorted(range(N), key=lambda i: (-scores[i], i))
            selected = np.zeros(N, bool)
            selected[order[:k]] = True
        else:
            selected = np.ones(N, bool)

        # --- Eq. 5 probe distances and Eq. 7 ages ---
        m = np.zeros(N, np.float64)
        if uses_vaoi:
            if trained and t < t_stop:
                t_stop = t
            v = np.asarray(net.probe(glob, images[:, :probe]), np.float64)
            for i in range(N):
                m[i] = np.sqrt(np.sum((v[i] - h[i]) ** 2))
            decide = m >= mu
            free = ~selected
            if m_log is not None and t < t_stop:
                m_log.append(m.copy())
            if t < t_stop and witness is not None:
                gaps = np.abs(m - mu)[free]
                if gaps.size:
                    min_margin = min(min_margin, float(gaps.min()))
                amb = free & (np.abs(m - mu) < margin)
                base = int(np.sum((age + decide)[free & ~amb]) + np.sum(age[amb]))
                r = int(witness[t]) - base
                if 0 <= r <= amb.sum() and (r == 0 or r == amb.sum()):
                    decide[amb] = r > 0
                else:
                    if not (0 <= r <= amb.sum()):
                        witness_faults += 1
                    t_stop = t
            age = np.where(selected, 0, age + decide.astype(np.int64))

        # --- slot-level energy dynamics, client by client ---
        started = np.zeros(N, bool)
        uploaded = np.zeros(N, bool)
        energy = 0
        pending_in = pending.copy()
        for i in range(N):
            b, pend, start_slot = int(battery[i]), bool(pending[i]), None
            for s in range(S):
                b = min(b + int(charges[s, i]), e_max)
                busy = start_slot is not None and start_slot <= s < start_slot + kappa
                if (selected[i] and start_slot is None and not busy and not pend
                        and b >= kappa and s <= S - kappa):
                    start_slot, b = s, b - kappa
                    energy += kappa
                busy = start_slot is not None and start_slot <= s < start_slot + kappa
                done_now = start_slot is not None and s + 1 == start_slot + kappa
                pend = pend or done_now
                if pend and not busy and not done_now and b >= 1 and not uploaded[i]:
                    b -= 1
                    energy += 1
                    pend = False
                    uploaded[i] = True
            battery[i], pending[i], started[i] = b, pend, start_slot is not None

        # --- local training (kappa SGD steps over one permutation pass) ---
        new_msgs = list(msgs)
        for i in np.flatnonzero(started):
            params, fsum = glob, jnp.zeros((model["num_classes"],), jnp.float32)
            perm = jax.random.permutation(train_keys[i], pool)
            idx = perm[: kappa * bs].reshape(kappa, bs)
            xs, ys = images[i][idx], labels[i][idx]
            for step in range(kappa):
                params, fsum = net.sgd_step(params, fsum, xs, ys, step)
            new_msgs[i] = params
            if uses_vaoi:
                h[i] = np.asarray(fsum, np.float64) / (kappa * bs)
        trained = trained or bool(started.any())

        # --- FedAvg over this epoch's uploads (old message if it entered pending) ---
        ups = [msgs[i] if pending_in[i] else new_msgs[i] for i in np.flatnonzero(uploaded)]
        if set(last_ups) & set(np.flatnonzero(started).tolist()):
            last_ups = []  # a message that went into the model has been replaced
        if ups:
            glob = _mean_of(ups)
            last_ups = np.flatnonzero(uploaded).tolist()
        msgs = new_msgs

        rows["energy"].append(energy)
        rows["age_sum"].append(int(age.sum()))
        rows["avg_age"].append(float(age.sum()) / N)
        rows["n_started"].append(int(started.sum()))
        rows["n_uploaded"].append(int(uploaded.sum()))
        rows["avg_m"].append(float(m.sum()) / N)

        if (t + 1) % eval_every == 0 or t + 1 == epochs:
            preds = np.asarray(net.predict(glob, test_images))
            f1s.append(macro_f1(preds, test_labels, model["num_classes"]))

    return {
        "metrics": {k_: np.asarray(v) for k_, v in rows.items()},
        "f1": np.asarray(f1s),
        "init_params": {n: np.asarray(v, np.float32) for n, v in init.items()},
        "global_params": {n: np.asarray(v.astype(jnp.float32)) for n, v in glob.items()},
        "msg_params": {n: np.stack([np.asarray(m[n].astype(jnp.float32)) for m in msgs])
                       for n in init},
        "final": {"age": age, "battery": battery, "pending": pending},
        "last_uploads": np.asarray(last_ups, np.int64),
        "t_stop": t_stop,
        "witness_faults": witness_faults,
        "min_margin": float(min_margin),
    }


def eval_f1(model: dict, params: dict, data: Dict[str, Any]) -> float:
    """Macro-F1 of ``params`` on the test set, by the reference's forward."""
    net = Model(model, REFERENCE, 0.0)
    p = {n: jnp.asarray(v, jnp.float32) for n, v in params.items()}
    preds = np.asarray(net.predict(p, data["test_images"]))
    return macro_f1(preds, np.asarray(data["test_labels"]), model["num_classes"])
