"""A task that is not an image task, for the harness's own tests:
next-token prediction on int32 token batches (B, seq_len) through
``FederatedTrainer``, with a small plain-``jnp`` model (an embedding, a
tanh, a dense head over the vocabulary).

Its data has fields of its own in the traffic file (``TRAFFIC_FIELDS``):
the sequence length, the slice of the vocabulary its ids come from, and
the sequences of each domain. A domain plays the part of an image
task's class: the Dirichlet partition spreads each domain's sequences
over the clients. It counts the work of its local scope (``work``) and
names the round program's scopes (``scopes``)."""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import fl
import tracing
import traffic as traffic_mod
from traffic import Traffic

from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer
from repro.core.baselines import FedDPCHyper
from repro.core.samplers import ClientSampler
from repro.ingest import ListDataSource

TRAFFIC_FIELDS = {"seq_len": int, "vocab": int, "sequences_per_domain": int}
SCOPES = ("fl.local", "fl.server")
NEVER = 10 ** 9
I32 = F32 = 4


def init_params(model: Dict[str, Any], seed: int):
    v, d = model["vocab_size"], model["d_model"]
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"embed": jax.random.normal(k1, (v, d), jnp.float32) * 0.5,
            "head": {"w": jax.random.normal(k2, (d, v), jnp.float32)
                     / np.sqrt(d),
                     "b": jnp.zeros((v,), jnp.float32)}}


def loss(params, batch, precision=jax.lax.Precision.DEFAULT,
         dtype=jnp.float32):
    """Mean cross-entropy of each next token."""
    h = jnp.tanh(params["embed"][batch["x"]].astype(dtype))
    logits = (jnp.matmul(h, params["head"]["w"].astype(dtype),
                         precision=precision)
              + params["head"]["b"].astype(dtype)).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def token_flops(model: Dict[str, Any]) -> int:
    """Training operations of one token: the head's matmul forward
    (2 d V) and its two gradients (4 d V); the embedding is a gather."""
    return 6 * model["d_model"] * model["vocab_size"]


class ScheduleSampler(ClientSampler):
    def __init__(self, traffic: Traffic):
        self._rng = traffic_mod.arrivals_rng(traffic)
        self._schedule = traffic_mod.CohortSchedule(
            traffic.num_clients, traffic.clients_per_round)

    def sample(self, rng, round):
        return self._schedule.next(self._rng)


class TokenTask:
    def __init__(self, config: Dict[str, Any], traffic: Traffic, seed: int):
        self.model, self.traffic = config["model"], traffic
        data = traffic.data
        if data["vocab"] > self.model["vocab_size"]:
            raise ValueError("the traffic's ids lie outside the vocabulary")
        w_tok, w_init, trainer_seed = traffic_mod.seed_words(seed, 3)
        self.domains, self.parts = traffic_mod.population(
            traffic, self.model["domains"], data["sequences_per_domain"])
        # each domain walks the vocabulary slice by a step of its own
        rng = np.random.RandomState(w_tok)
        start = rng.randint(0, data["vocab"], len(self.domains))
        pos = np.arange(data["seq_len"] + 1)
        self.tokens = ((start[:, None] + (1 + self.domains[:, None]) * pos
                        + rng.randint(0, 2, (len(self.domains), len(pos))))
                       % data["vocab"]).astype(np.int32)
        params = init_params(self.model, w_init)
        self.params0 = jax.device_get(params)
        self.num_params = sum(x.size for x in jax.tree.leaves(self.params0))
        self.max_steps = traffic_mod.max_steps(self.parts, traffic)
        algo = AlgoConfig(name="feddpc", eta_l=traffic.eta_l,
                          eta_g=traffic.eta_g, local_optimizer="sgd",
                          hyper=FedDPCHyper(lam=traffic.lam))
        cfg = ExecConfig(rounds=NEVER, clients_per_round=
                         traffic.clients_per_round, seed=trainer_seed,
                         eval_every=NEVER, async_eval=False,
                         shard_clients=traffic.shard_clients,
                         batch_size=traffic.batch_size,
                         local_epochs=traffic.local_epochs)
        self.trainer = FederatedTrainer(
            loss, params, traffic.num_clients,
            ListDataSource(lambda c, t: [
                {"x": x, "y": y} for x, y in self.batches(c, t)]),
            cfg, None, algo=algo, sampler=ScheduleSampler(traffic))
        self.trainer._max_batches = self.max_steps
        self._program: Dict[str, Any] = {"losses": []}
        jitted, self._round_args = self.trainer._cohort_round, None

        def round_call(*args):
            if self._round_args is None:
                self._round_args = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
                    args)
            return jitted(*args)

        self._round_jit = jitted
        self.trainer._cohort_round = round_call

    chips_used = 1

    @property
    def samples_per_step(self) -> int:
        return self.traffic.batch_size

    @property
    def flops_per_sample(self) -> int:
        return self.traffic.data["seq_len"] * token_flops(self.model)

    def useful_steps(self, t: int) -> int:
        return traffic_mod.useful_steps(self.parts, self.trainer.schedule[t],
                                        self.traffic)

    def computed_steps(self) -> int:
        return self.traffic.clients_per_round * self.max_steps

    def epilogue_bytes(self):
        return None           # the jnp epilogue: no kernel to hold

    def kernels(self):
        return {}

    def scopes(self):
        return tracing.scope_tests(
            self._round_jit.lower(*self._round_args).compile().as_text(),
            SCOPES)

    def work(self, rounds: List[int]) -> Dict[str, Dict[str, int]]:
        """The local scope's operations and bytes in the listed rounds:
        each useful local step's tokens, and per step the token ids
        read (inputs and targets) and the weights read and written."""
        steps = sum(self.useful_steps(t) for t in rounds)
        tokens = self.traffic.batch_size * self.traffic.data["seq_len"]
        return {"fl.local": {
            "flops": steps * tokens * token_flops(self.model),
            "bytes": steps * (2 * tokens * I32
                              + 2 * self.num_params * F32)}}

    def memory_note(self) -> str:
        return "tokens fixture"

    def run_round(self, t: int):
        rec = self.trainer.run_round(t)
        if t < 3:
            self._program["losses"].append(rec.train_loss)
            if t == 0:
                self._program["first_update"] = jax.device_get(
                    self.trainer.server_state["delta_prev"])
            if t == 2:
                self._program["params"] = jax.device_get(self.trainer.params)
                self._program["cohorts"] = [np.asarray(c) for c in
                                            self.trainer.schedule[:3]]
        return rec

    def close(self):
        self.trainer.close()
        self.trainer.params = self.trainer.server_state = None

    def batches(self, client: int, t: int):
        return [(self.tokens[sel, :-1], self.tokens[sel, 1:]) for sel in
                traffic_mod.batch_selections(
                    self.parts[client], self.traffic.batch_size, client, t,
                    self.traffic.local_epochs)]

    def cohorts(self, rounds: int) -> List[np.ndarray]:
        rng = traffic_mod.arrivals_rng(self.traffic)
        sched = traffic_mod.CohortSchedule(self.traffic.num_clients,
                                           self.traffic.clients_per_round)
        return [sched.next(rng) for _ in range(rounds)]

    def reference(self, precision: str = "reference", half_batch=False):
        dtype, prec = fl.PRECISIONS[precision]
        step = fl.make_local_step(
            lambda w, x, y: loss(w, {"x": x, "y": y}, prec, dtype),
            self.traffic.eta_l, dtype, half_batch=half_batch)
        t = self.traffic
        return fl.reference_rounds(step, self.params0, self.cohorts(3),
                                   self.batches, t.eta_l, t.eta_g, t.lam,
                                   dtype)

    def program(self):
        return self._program

    def compare(self, program, reference):
        numbers = fl.compare(program, reference, self.params0)
        numbers["cohort_mismatch"] = float(sum(
            int(np.sum(np.asarray(a) != b)) for a, b in
            zip(program["cohorts"], self.cohorts(3))))
        return numbers


def build(config: Dict[str, Any], traffic: Traffic, seed: int) -> TokenTask:
    return TokenTask(config, traffic, seed)
