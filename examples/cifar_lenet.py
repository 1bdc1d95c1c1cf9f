"""Baseline config #2: federated LeNet on CIFAR-10-shaped data.

100 simulated participants (8 sum + 12 update per round drawn from the
pool), f32 mask config, LeNet local training. Synthetic CIFAR-shaped data
stands in for the dataset (zero-egress environment).

Run:  python examples/cifar_lenet.py [--rounds 2] [--participants 20]
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import threading
import time
from fractions import Fraction

import numpy as np

sys.path.insert(0, ".")

import jax

from xaynet_tpu.models import lenet
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


def synthetic_cifar(seed: int, n: int = 128, image_size: int = 32):
    """CIFAR-shaped data with a shared linear teacher so the federated
    objective is actually learnable (labels = argmax of a fixed random
    projection of the image)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, image_size, image_size, 3)).astype(np.float32)
    teacher = np.random.default_rng(123).normal(size=(image_size * image_size * 3, 10))
    y = np.argmax(x.reshape(n, -1) @ teacher, axis=1).astype(np.int32)
    return x, y


def start_coordinator(model_len: int, n_sum: int, n_update: int, quant: int = 0):
    settings = Settings(
        pet=PetSettings(
            sum=PhaseSettings(prob=0.2, count=CountSettings(n_sum, n_sum), time=TimeSettings(0, 300)),
            update=PhaseSettings(prob=0.5, count=CountSettings(n_update, n_update), time=TimeSettings(0, 300)),
            sum2=Sum2Settings(count=CountSettings(n_sum, n_sum), time=TimeSettings(0, 300)),
        )
    )
    settings.model.length = model_len
    # pre-mask quantization (docs/DESIGN.md §17): a coarser fixed-point
    # config — smaller group order, fewer limbs, proportionally cheaper
    # masks/folds/transfers. Participants follow via the round params.
    settings.mask.quant = quant
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
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--participants", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=32, help="synthetic image side (CI smoke: 8)")
    ap.add_argument("--epochs", type=int, default=1, help="local epochs per round")
    ap.add_argument("--lr", type=float, default=1e-3, help="local SGD learning rate")
    ap.add_argument("--check-loss", action="store_true",
                    help="exit nonzero unless the final global model beats the init loss")
    ap.add_argument("--quant", type=int, default=0,
                    help="pre-mask quantization level (0 = exact catalogue "
                    "config; level q divides the fixed-point scale by 10^q "
                    "and shrinks the group order/limb count). The "
                    "--check-loss gate is the accuracy gate for quantized "
                    "rounds: federation must still beat the init loss.")
    args = ap.parse_args()

    image_shape = (args.image_size, args.image_size, 3)
    template = lenet.init_params(jax.random.PRNGKey(0), image_shape=image_shape)
    model_len = model_length(template)
    n_sum, n_update = 2, max(3, args.participants - 2)
    print(f"LeNet: {model_len} parameters; {n_sum} sum + {n_update} update per round")

    url = start_coordinator(model_len, n_sum, n_update, quant=args.quant)
    probe = HttpClient(url)

    def sync(coro):
        return asyncio.run(coro)

    shared_step = lenet.make_train_step(learning_rate=args.lr)
    last_seed = None
    threads = []
    for round_no in range(1, args.rounds + 1):
        t0 = time.time()
        params = sync(probe.get_round_params())
        while last_seed is not None and params.seed.as_bytes() == last_seed:
            time.sleep(0.2)
            params = sync(probe.get_round_params())
        seed = params.seed.as_bytes()

        def kwargs(i):
            return dict(
                init_params_fn=lambda: lenet.init_params(jax.random.PRNGKey(1), image_shape=image_shape),
                make_step=lambda: shared_step,
                data=synthetic_cifar(i, image_size=args.image_size),
                epochs=args.epochs,
                batch_size=32,
            )

        for i in range(n_sum):
            threads.append(
                spawn_participant(
                    url, FederatedTrainer, kwargs=kwargs(900 + i),
                    keys=keys_for_task(seed, 0.2, 0.5, "sum", start=i * 1000),
                )
            )
        for i in range(n_update):
            threads.append(
                spawn_participant(
                    url, FederatedTrainer, kwargs=kwargs(i), scalar=Fraction(1, n_update),
                    keys=keys_for_task(seed, 0.2, 0.5, "update", start=(500 + i) * 1000),
                )
            )

        while True:
            model = sync(probe.get_model())
            fresh = sync(probe.get_round_params())
            if model is not None and fresh.seed.as_bytes() != seed:
                break
            time.sleep(0.2)
        last_seed = seed
        print(f"round {round_no}: completed in {time.time() - t0:.1f}s "
              f"(model norm {float(np.linalg.norm(model)):.2f})")

    for t in threads:
        t.stop()

    if args.check_loss:
        from eval_check import require_loss_improved

        model_obj, _, _ = shared_step
        # the shared linear teacher makes every shard the same task
        require_loss_improved(
            model_obj,
            template,
            lenet.init_params(jax.random.PRNGKey(1), image_shape=image_shape),
            model,
            [synthetic_cifar(i, image_size=args.image_size) for i in range(n_update)],
        )


if __name__ == "__main__":
    main()
