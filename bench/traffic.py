"""The benchmark's own traffic: a federated population and the cohorts
that arrive at the server, made from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only;
this one generator reads every such file. The fields every FedDPC cell
has are ``Traffic``'s own; the fields of a task's data (an image task's
samples per class and pixel noise, a token task's sequence length) are
declared by the task module in its ``TRAFFIC_FIELDS`` table and loaded
into ``Traffic.data``. Two seeds are kept apart:

* ``population_seed`` (in the file) fixes the deployment: the class of
  every sample and the Dirichlet partition of samples over clients. So
  the clients' sizes, and with them the shape bucket M and the useful
  share of the local steps, are the same in every run of a cell.
* ``--seed`` (per run) draws the data's content (pixels, tokens) and
  the initial weights.

The order in which clients arrive comes from the population seed too
(``arrivals_rng``): every run of a cell trains the same cohorts in the
same order, so the useful work a window holds does not change with
``--seed``.

The partition rule, the batch rule and the image model are copies of
``repro.data.dirichlet.dirichlet_partition``,
``repro.ingest.images.iter_batch_selections`` and
``repro.data.synthetic.make_image_dataset``: the benchmark reads none of
them from the program, so a change there cannot move its yardstick.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field, fields
from pathlib import Path
from types import MappingProxyType
from typing import Any, List, Mapping, Tuple, get_type_hints

import numpy as np


@dataclass(frozen=True)
class Traffic:
    """One traffic mix: the federated job a cell runs. ``data`` holds the
    task's own fields, as its module's ``TRAFFIC_FIELDS`` declares them."""
    name: str
    num_clients: int
    clients_per_round: int
    batch_size: int
    local_epochs: int
    dirichlet_alpha: float
    population_seed: int
    eta_l: float
    eta_g: float
    lam: float
    shard_clients: bool
    data: Mapping[str, Any] = field(default_factory=dict)

    @classmethod
    def load(cls, path: Path, task_fields: Mapping[str, type]
             ) -> "Traffic":
        """The traffic file at ``path``, strictly: every common field and
        every field the task declares, each of its declared type, and no
        other key but ``why`` and ``assumed``."""
        raw = json.loads(Path(path).read_text())
        types = get_type_hints(cls)
        common = {f.name: types[f.name] for f in fields(cls)
                  if f.name not in ("name", "data")}
        clash = common.keys() & task_fields.keys()
        if clash:
            raise ValueError(f"the task declares common traffic fields "
                             f"{sorted(clash)}")
        want = {**common, **task_fields}
        missing = want.keys() - raw.keys()
        extra = raw.keys() - want.keys() - {"why", "assumed"}
        if missing or extra:
            raise ValueError(f"{path}: missing {sorted(missing)}, "
                             f"unknown {sorted(extra)}")
        values = {k: _typed(path, k, raw[k], t) for k, t in want.items()}
        return cls(name=Path(path).stem,
                   data=MappingProxyType({k: values.pop(k)
                                          for k in task_fields}),
                   **values)


def _typed(path, key: str, value, kind: type):
    """``value`` as a ``kind``; a whole number stands for a float, a
    bool for nothing but a bool."""
    if kind is float and isinstance(value, int) \
            and not isinstance(value, bool):
        return float(value)
    if isinstance(value, bool) != (kind is bool) \
            or not isinstance(value, kind):
        raise ValueError(f"{path}: {key} is {value!r}, not of type "
                         f"{kind.__name__}")
    return value


def seed_words(seed: int, n: int) -> List[int]:
    """n independent 31-bit words from a seed of any size."""
    return [int(w) & 0x7FFFFFFF
            for w in np.random.SeedSequence(int(seed)).generate_state(n)]


# ---- the population: copied from repro.data.dirichlet ----

def dirichlet_partition(labels: np.ndarray, num_clients: int, alpha: float,
                        seed: int, min_size: int = 2) -> List[np.ndarray]:
    """For each class r draw P_r ~ Dir(alpha) and give client j the
    P_{r,j} share of class r's samples (FedDPC §5.1)."""
    rng = np.random.RandomState(seed)
    classes = np.unique(labels)
    for _ in range(100):
        client_idx = [[] for _ in range(num_clients)]
        for r in classes:
            idx_r = np.where(labels == r)[0]
            rng.shuffle(idx_r)
            p = rng.dirichlet(np.full(num_clients, alpha))
            cuts = (np.cumsum(p) * len(idx_r)).astype(int)[:-1]
            for j, part in enumerate(np.split(idx_r, cuts)):
                client_idx[j].extend(part.tolist())
        if min(len(c) for c in client_idx) >= min_size:
            break
    else:
        for j in range(num_clients):
            while len(client_idx[j]) < min_size:
                donor = max(range(num_clients),
                            key=lambda i: len(client_idx[i]))
                if donor == j or len(client_idx[donor]) <= min_size:
                    break
                client_idx[j].append(client_idx[donor].pop())
    return [np.asarray(sorted(c), dtype=np.int64) for c in client_idx]


def population(traffic: Traffic, num_classes: int, samples_per_class: int
               ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """(labels, client index arrays), fixed by the population seed:
    ``samples_per_class`` samples of each class, shuffled, split over
    the clients by the Dirichlet rule."""
    rng = np.random.RandomState(traffic.population_seed)
    labels = np.repeat(np.arange(num_classes, dtype=np.int32),
                       samples_per_class)
    labels = labels[rng.permutation(len(labels))]
    parts = dirichlet_partition(labels, traffic.num_clients,
                                traffic.dirichlet_alpha,
                                seed=traffic.population_seed)
    return labels, parts


def images_for(labels: np.ndarray, image_size: int, channels: int,
               num_classes: int, noise: float, seed: int,
               chunk: int = 5000) -> np.ndarray:
    """Class-conditional images (N, H, W, C) f32: a per-class frequency
    template, a per-image gain jitter and Gaussian pixel noise, clipped
    to [-3, 3] (the image model of repro.data.synthetic). Drawn on the
    default device, ``chunk`` images per call of one jitted program, and
    copied to the host, where the program's data source reads them."""
    import jax
    import jax.numpy as jnp

    h = w = image_size
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    templates = np.empty((num_classes, h, w, channels), np.float32)
    for c in range(num_classes):
        fx, fy = 1 + (c % 5), 1 + ((c // 5) % 5)
        phase = 2 * np.pi * (c / num_classes)
        base = (np.sin(2 * np.pi * fx * xx / w + phase)
                * np.cos(2 * np.pi * fy * yy / h))
        templates[c] = np.stack([np.roll(base, shift=k * 3, axis=1)
                                 for k in range(channels)], axis=-1)

    @jax.jit
    def draw(key, lab, tmpl):
        k1, k2 = jax.random.split(key)
        gain = 1.0 + 0.2 * jax.random.normal(k1, (lab.shape[0], 1, 1, 1))
        pix = jax.random.normal(k2, (lab.shape[0], h, w, channels))
        return jnp.clip(tmpl[lab] * gain + noise * pix, -3.0, 3.0)

    n = len(labels)
    out = np.empty((n, h, w, channels), np.float32)
    padded = np.zeros(-(-n // chunk) * chunk, np.int32)
    padded[:n] = labels
    key, tmpl = jax.random.key(seed), jnp.asarray(templates)
    for i in range(0, n, chunk):
        got = draw(jax.random.fold_in(key, i), padded[i:i + chunk], tmpl)
        out[i:i + chunk] = np.asarray(got)[: n - i]
    return out


# ---- the batch rule: copied from repro.ingest.images ----

def batch_selections(idx: np.ndarray, batch_size: int, client: int,
                     round_num: int, local_epochs: int) -> List[np.ndarray]:
    """The sample indices of each local step of one client in one round:
    shuffled per local epoch by RandomState(hash((client, round))), a
    shard smaller than a batch wrap-padded to one full batch, the
    remainder dropped."""
    rng = np.random.RandomState(hash((int(client), int(round_num)))
                                % (2 ** 31))
    out = []
    for _ in range(local_epochs):
        order = rng.permutation(len(idx))
        n = len(order)
        if n < batch_size:
            order = np.resize(order, batch_size)
            n = batch_size
        for start in range(0, n - batch_size + 1, batch_size):
            out.append(idx[order[start:start + batch_size]])
    return out


def steps_of(size: int, batch_size: int, local_epochs: int) -> int:
    """Local steps of a client that holds ``size`` samples."""
    return local_epochs * max(size // batch_size, 1)


def max_steps(parts: List[np.ndarray], traffic: Traffic) -> int:
    """The population's largest client in steps: the M every round is
    padded to, fixed before warm-up so no round grows it."""
    return max(steps_of(len(p), traffic.batch_size, traffic.local_epochs)
               for p in parts)


# ---- arrivals: which clients each round samples ----

def arrivals_rng(traffic: Traffic) -> np.random.RandomState:
    """The generator of a cell's arrivals, from its population seed."""
    return np.random.RandomState(seed_words(traffic.population_seed, 1)[0])


class CohortSchedule:
    """Cohorts drawn without replacement from a stream of shuffled
    passes over the population: every client is sampled once per pass,
    in an order the generator draws.
    A cohort that would straddle two passes and repeat a client takes
    the next unused clients of the new pass instead."""

    def __init__(self, num_clients: int, cohort: int):
        if not 1 <= cohort <= num_clients:
            raise ValueError((cohort, num_clients))
        self.num_clients, self.cohort = num_clients, cohort
        self._queue: List[int] = []

    def next(self, rng: np.random.RandomState) -> np.ndarray:
        if len(self._queue) < self.cohort:
            self._queue.extend(rng.permutation(self.num_clients).tolist())
        picked: List[int] = []
        seen = set()
        i = 0
        while len(picked) < self.cohort:
            c = self._queue[i]
            if c in seen:
                i += 1
                continue
            picked.append(c)
            seen.add(c)
            del self._queue[i]
        return np.asarray(picked, np.int64)


def useful_steps(parts: List[np.ndarray], cohort: np.ndarray,
                 traffic: Traffic) -> int:
    """Unmasked local steps of one sampled cohort."""
    return sum(steps_of(len(parts[int(c)]), traffic.batch_size,
                        traffic.local_epochs) for c in cohort)
