"""Baseline config #1: federated house-prices regression (MLP, 10 participants).

Analogue of the reference's keras_house_prices example
(bindings/python/examples/keras_house_prices/): one coordinator, ten
participants each holding a private shard of the dataset, training a
2-hidden-layer MLP with federated averaging over the PET protocol.

Synthetic data stands in for the Kaggle dataset (zero-egress environment);
swap ``make_data`` for a real loader.

Run:  python examples/house_prices.py
"""

from __future__ import annotations

import asyncio
import sys
import threading
import time
from fractions import Fraction

import jax
import numpy as np

sys.path.insert(0, ".")

from xaynet_tpu.models import mlp
from xaynet_tpu.models.federated import FederatedTrainer, model_length
from xaynet_tpu.sdk.api import spawn_participant
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.server.rest import RestServer
from xaynet_tpu.server.services import Fetcher, PetMessageHandler
from xaynet_tpu.server.settings import (
    CountSettings,
    PhaseSettings,
    PetSettings,
    Settings,
    Sum2Settings,
    TimeSettings,
)
from xaynet_tpu.server.state_machine import StateMachineInitializer
from xaynet_tpu.storage.memory import (
    InMemoryCoordinatorStorage,
    InMemoryModelStorage,
    NoOpTrustAnchor,
)
from xaynet_tpu.storage.traits import Store

N_PARTICIPANTS = 10
N_SUM = 2
N_UPDATE = 6
ROUNDS = 3
INPUT_DIM = 13


def make_data(rng, n=256):
    """Synthetic housing-style regression data."""
    x = rng.normal(size=(n, INPUT_DIM)).astype(np.float32)
    w = rng.normal(size=INPUT_DIM).astype(np.float32)
    y = (x @ w + 0.1 * rng.normal(size=n)).astype(np.float32)
    return x, y


def start_coordinator(model_len: int):
    settings = Settings(
        pet=PetSettings(
            sum=PhaseSettings(prob=0.3, count=CountSettings(N_SUM, N_SUM), time=TimeSettings(0, 60)),
            update=PhaseSettings(prob=0.7, count=CountSettings(N_UPDATE, N_UPDATE), time=TimeSettings(0, 60)),
            sum2=Sum2Settings(count=CountSettings(N_SUM, N_SUM), time=TimeSettings(0, 60)),
        )
    )
    settings.model.length = model_len
    info, started = {}, threading.Event()

    def run():
        async def main():
            store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
            machine, tx, events = await StateMachineInitializer(settings, store).init()
            rest = RestServer(Fetcher(events), PetMessageHandler(events, tx))
            host, port = await rest.start("127.0.0.1", 0)
            info["url"] = f"http://{host}:{port}"
            started.set()
            await machine.run()

        asyncio.run(main())

    threading.Thread(target=run, daemon=True).start()
    started.wait(10)
    return info["url"]


def main():
    rng = np.random.default_rng(0)
    template = mlp.init_params(jax.random.PRNGKey(0), INPUT_DIM)
    model_len = model_length(template)
    print(f"model length: {model_len} parameters")

    url = start_coordinator(model_len)
    probe = HttpClient(url)

    def sync(coro):
        return asyncio.run(coro)

    # Task eligibility re-draws every round (fresh seed), so the simulation
    # pins role-matched participants per round; threads from earlier rounds
    # stay alive (they idle or pick up whatever role the new seed gives them).
    shared_step = mlp.make_train_step()
    threads = []
    last_seed = None
    for round_no in range(1, ROUNDS + 1):
        params = sync(probe.get_round_params())
        while last_seed is not None and params.seed.as_bytes() == last_seed:
            time.sleep(0.2)
            params = sync(probe.get_round_params())
        seed = params.seed.as_bytes()

        trainers = []
        for i in range(N_SUM):
            keys = keys_for_task(seed, 0.3, 0.7, "sum", start=i * 1000)
            threads.append(
                spawn_participant(
                    url,
                    FederatedTrainer,
                    kwargs=dict(
                        init_params_fn=lambda: mlp.init_params(jax.random.PRNGKey(1), INPUT_DIM),
                        make_step=lambda: shared_step,
                        data=make_data(rng),
                    ),
                    keys=keys,
                )
            )
        for i in range(N_UPDATE):
            keys = keys_for_task(seed, 0.3, 0.7, "update", start=(50 + i) * 1000)
            t = spawn_participant(
                url,
                FederatedTrainer,
                kwargs=dict(
                    init_params_fn=lambda i=i: mlp.init_params(jax.random.PRNGKey(10 + i), INPUT_DIM),
                    make_step=lambda: shared_step,
                    data=make_data(rng),
                    epochs=2,
                ),
                scalar=Fraction(1, N_UPDATE),
                keys=keys,
            )
            threads.append(t)
            trainers.append(t)

        deadline = time.time() + 120
        while time.time() < deadline:
            model = sync(probe.get_model())
            fresh = sync(probe.get_round_params())
            if model is not None and fresh.seed.as_bytes() != seed:
                break
            time.sleep(0.2)
        last_seed = seed
        losses = [t._participant.last_loss for t in trainers if t._participant.last_loss]
        print(f"round {round_no}: global model ready; local losses: "
              + ", ".join(f"{l:.4f}" for l in losses))

    for t in threads:
        t.stop()
    print("done")


if __name__ == "__main__":
    main()
