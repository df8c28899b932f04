"""Task builder for the paper's vision configurations (LeNet-5,
ResNet-18-GN): the system under test, built from a configuration file
and a traffic file the way ``repro.launch.train.build_vision_task``
builds it, fed only arrays this benchmark generated.

The harness loads the traffic file with the fields ``TRAFFIC_FIELDS``
declares, calls ``build(config, traffic, seed)`` and drives what it
returns; everything here that is not the program (data, weights,
cohorts, the reference) comes from the benchmark's own files.
"""
from __future__ import annotations

import base64
import functools
import re
from typing import Any, Dict, List, Optional

import jax
import numpy as np

import fl
import flops
import models
import tracing
import traffic as traffic_mod
from traffic import Traffic

from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer
from repro.core.baselines import FedDPCHyper
from repro.core.samplers import ClientSampler
from repro.ingest import FederatedImageData, StreamingImageSource
from repro.models.vision import VisionConfig, vision_loss_fn

# this task's own traffic fields: the images of each class, and the
# standard deviation of their Gaussian pixel noise
TRAFFIC_FIELDS = {"samples_per_class": int, "image_noise": float}
# the round program's stage scopes (repro.trace)
SCOPES = ("fl.local", "fl.codec", "fl.guard", "fl.server",
          "fl.server.reduce", "fl.server.epilogue")
NEVER = 10 ** 9      # rounds horizon and eval cadence: no end, no eval
# a Mosaic call's serialized kernel, and the epilogue kernel's function
# name in it (not the dequantizing variant's)
BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
EPILOGUE_KERNEL = re.compile(rb"(?<![A-Za-z0-9_])_batched_epilogue_kernel"
                             rb"(?![A-Za-z0-9_])")


def epilogue_calls(hlo_text: str) -> set:
    """Names of the ``tpu_custom_call`` instructions of a compiled
    program's text whose serialized Mosaic body holds the epilogue
    kernel's function name."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line \
                or " = " not in line:
            continue
        body = BODY.search(line)
        if body and EPILOGUE_KERNEL.search(base64.b64decode(body.group(1))):
            names.add(line.split(" = ", 1)[0].strip().lstrip("%"))
    return names


class ScheduleSampler(ClientSampler):
    """The benchmark's arrivals (traffic.CohortSchedule) behind the
    program's sampler interface. The order comes from the traffic's
    population seed, not from the trainer's RNG: every run of a cell
    trains the same cohorts in the same order, whatever its seed."""

    def __init__(self, traffic: Traffic):
        self._rng = traffic_mod.arrivals_rng(traffic)
        self._schedule = traffic_mod.CohortSchedule(
            traffic.num_clients, traffic.clients_per_round)

    def sample(self, rng, round):
        return self._schedule.next(self._rng)


class VisionTask:
    """One run's federated job: the trainer, and what the benchmark
    knows about it independently of the program."""

    def __init__(self, config: Dict[str, Any], traffic: Traffic, seed: int):
        self.model = config["model"]
        self.traffic = traffic
        w_img, w_init, self.trainer_seed = traffic_mod.seed_words(seed, 3)
        self.labels, self.parts = traffic_mod.population(
            traffic, self.model["num_classes"],
            traffic.data["samples_per_class"])
        self.images = traffic_mod.images_for(
            self.labels, self.model["image_size"], self.model["channels"],
            self.model["num_classes"], traffic.data["image_noise"], w_img)
        params = models.init_params(self.model, w_init)
        self.params0 = jax.device_get(params)
        self.num_params = sum(x.size for x in jax.tree.leaves(self.params0))
        if self.num_params != config["params"]:
            raise ValueError(f"{config['name']}: {self.num_params} params, "
                             f"the configuration states {config['params']}")
        self.max_steps = traffic_mod.max_steps(self.parts, traffic)
        vc = VisionConfig(name=config["name"], family=self.model["family"],
                          image_size=self.model["image_size"],
                          channels=self.model["channels"],
                          num_classes=self.model["num_classes"],
                          width=self.model.get("width", 64),
                          groups=self.model.get("groups", 8))
        empty = np.zeros((0,) + self.images.shape[1:], np.float32)
        data = FederatedImageData(self.images, self.labels, empty,
                                  np.zeros(0, np.int32), self.parts)
        source = StreamingImageSource(data, traffic.batch_size,
                                      traffic.local_epochs)
        algo = AlgoConfig(
            name="feddpc", eta_l=traffic.eta_l, eta_g=traffic.eta_g,
            local_optimizer="sgd",
            hyper=FedDPCHyper(lam=traffic.lam, use_kernel=True))
        cfg = ExecConfig(rounds=NEVER, clients_per_round=
                         traffic.clients_per_round, seed=self.trainer_seed,
                         eval_every=NEVER, async_eval=False,
                         shard_clients=traffic.shard_clients,
                         batch_size=traffic.batch_size,
                         local_epochs=traffic.local_epochs)
        self.trainer = FederatedTrainer(
            functools.partial(vision_loss_fn, vc), params,
            traffic.num_clients, source, cfg, None, algo=algo,
            sampler=ScheduleSampler(traffic))
        del params
        # the shape bucket is the population's largest client, fixed
        # before the first round: no round in the window can grow it
        self.trainer._max_batches = self.max_steps
        self._program: Dict[str, Any] = {"losses": []}
        # the abstract arguments of the round program's first call, to
        # compile the same program again after the window and read what
        # the compiler says of it
        jitted, self._round_jit, self._round_args = (
            self.trainer._cohort_round, self.trainer._cohort_round, None)

        def round_call(*args):
            if self._round_args is None:
                self._round_args = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        np.shape(x), x.dtype,
                        sharding=getattr(x, "sharding", None)), args)
            return jitted(*args)

        self.trainer._cohort_round = round_call
        self._compiled = None

    # ---- what the harness reads ----

    @property
    def chips_used(self) -> int:
        mesh = self.trainer.mesh
        return 1 if mesh is None else int(mesh.devices.size)

    def useful_steps(self, t: int) -> int:
        """Unmasked local steps of round t's cohort, by the benchmark's
        copy of the batch rule."""
        return traffic_mod.useful_steps(self.parts, self.trainer.schedule[t],
                                        self.traffic)

    def computed_steps(self) -> int:
        """Local steps the fused round computes: every client, every
        slot of the shape bucket, padded or not."""
        k = self.traffic.clients_per_round
        return -(-k // self.chips_used) * self.chips_used * self.max_steps

    @property
    def samples_per_step(self) -> int:
        return self.traffic.batch_size

    @property
    def flops_per_sample(self) -> float:
        return flops.train_flops_per_sample(self.model)

    def epilogue_bytes(self) -> Optional[int]:
        """Bytes the server epilogue must move per round, or None where
        the round takes no epilogue kernel: a round partitioned over
        chips takes the program's jnp epilogue."""
        if self.chips_used > 1:
            return None
        return flops.epilogue_bytes(self.num_params,
                                    self.traffic.clients_per_round)

    def _round_program(self):
        if self._compiled is None:
            self._compiled = self._round_jit.lower(*self._round_args).compile()
        return self._compiled

    def memory_note(self) -> str:
        """What the compiler says of the round program's memory."""
        ma = self._round_program().memory_analysis()
        return (f"round program (compiler): temp {ma.temp_size_in_bytes}, "
                f"arguments {ma.argument_size_in_bytes}, outputs "
                f"{ma.output_size_in_bytes}, aliased "
                f"{ma.alias_size_in_bytes}, code "
                f"{ma.generated_code_size_in_bytes} bytes")

    def kernels(self) -> Dict[str, Any]:
        """Kernel name -> a test on a trace's op event name. The round's
        Pallas epilogue is one ``tpu_custom_call`` per weight leaf. The
        instruction takes its caller's name, not the kernel's; Mosaic
        keeps the kernel function's name in the call's serialized body,
        so the calls are found in the compiled program's text by that
        name, and their events by the instruction name."""
        names = epilogue_calls(self._round_program().as_text())

        def is_epilogue(event_name: str) -> bool:
            return event_name.split(" = ", 1)[0].lstrip("%") in names

        return {"batched_epilogue": is_epilogue}

    def scopes(self) -> Dict[str, Any]:
        """Program scope -> a test on a trace's op event name, for each
        of the round's stage scopes, from the compiled round's text."""
        return tracing.scope_tests(self._round_program().as_text(), SCOPES)

    def work(self, rounds: List[int]) -> Dict[str, Dict[str, int]]:
        """Operations and bytes the listed rounds require, by kernel:
        the server epilogue's, from shapes (bench/flops.py), where the
        round takes the epilogue kernel."""
        per_round = self.epilogue_bytes()
        if per_round is None:
            return {}
        k = self.traffic.clients_per_round
        return {"batched_epilogue": {
            "flops": flops.epilogue_flops(self.num_params, k) * len(rounds),
            "bytes": per_round * len(rounds)}}

    def run_round(self, t: int):
        """One round through the program's own entry point; the first
        three feed the comparison with the reference."""
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
        """Stops the staging thread and frees the program's device state."""
        self.trainer.close()
        self.trainer.params = self.trainer.server_state = None
        self._compiled = None

    # ---- the reference ----

    def cohorts(self, rounds: int) -> List[np.ndarray]:
        """The first cohorts, drawn anew from the traffic."""
        rng = traffic_mod.arrivals_rng(self.traffic)
        sched = traffic_mod.CohortSchedule(self.traffic.num_clients,
                                           self.traffic.clients_per_round)
        return [sched.next(rng) for _ in range(rounds)]

    def batches(self, client: int, t: int):
        return [(self.images[sel], self.labels[sel]) for sel in
                traffic_mod.batch_selections(
                    self.parts[client], self.traffic.batch_size, client, t,
                    self.traffic.local_epochs)]

    def reference(self, precision: str = "reference", half_batch=False
                  ) -> Dict[str, Any]:
        dtype, prec = fl.PRECISIONS[precision]
        loss = functools.partial(models.loss, self.model, dtype=dtype,
                                 precision=prec)
        step = fl.make_local_step(
            lambda w, x, y: loss(w, x, y), self.traffic.eta_l, dtype,
            half_batch=half_batch)
        t = self.traffic
        return fl.reference_rounds(step, self.params0, self.cohorts(3),
                                   self.batches, t.eta_l, t.eta_g, t.lam,
                                   dtype)

    def program(self) -> Dict[str, Any]:
        return self._program

    def compare(self, program: Dict[str, Any], reference: Dict[str, Any]
                ) -> Dict[str, float]:
        numbers = fl.compare(program, reference, self.params0)
        want = self.cohorts(3)
        numbers["cohort_mismatch"] = float(sum(
            int(np.sum(np.asarray(a) != b)) for a, b in
            zip(program["cohorts"], want)))
        return numbers


def build(config: Dict[str, Any], traffic: Traffic, seed: int) -> VisionTask:
    return VisionTask(config, traffic, seed)
