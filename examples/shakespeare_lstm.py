"""Baseline config #3: federated char-LSTM next-token (LEAF-Shakespeare shaped).

1k-participant-scale config with a bounded M3 mask; this simulation drives a
scaled-down round (pass --participants to widen). Character sequences are
synthesized with per-participant distributions standing in for the LEAF
shards.

Run:  python examples/shakespeare_lstm.py [--rounds 1] [--participants 8]
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

from xaynet_tpu.models import lstm
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

SEQ_LEN = 40
HIDDEN = 64


def synthetic_shards(seed: int, n: int = 64, seq_len: int = SEQ_LEN):
    """Per-participant character streams with distinct symbol biases."""
    rng = np.random.default_rng(seed)
    bias = rng.dirichlet(np.ones(lstm.VOCAB_SIZE) * 0.3)
    tokens = rng.choice(lstm.VOCAB_SIZE, size=(n, seq_len + 1), p=bias).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--participants", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=HIDDEN)
    ap.add_argument("--seq-len", type=int, default=SEQ_LEN)
    ap.add_argument("--check-loss", action="store_true",
                    help="exit nonzero unless the final global model beats the init loss")
    ap.add_argument("--epochs", type=int, default=1, help="local epochs per round")
    ap.add_argument("--lr", type=float, default=1e-3, help="local Adam learning rate")
    args = ap.parse_args()

    hidden, seq_len = args.hidden, args.seq_len
    template = lstm.init_params(jax.random.PRNGKey(0), seq_len=seq_len, hidden=hidden)
    model_len = model_length(template)
    n_sum, n_update = 1, max(3, args.participants - 1)
    print(f"char-LSTM: {model_len} parameters (bounded M3 mask config)")

    settings = Settings(
        pet=PetSettings(
            sum=PhaseSettings(prob=0.2, count=CountSettings(n_sum, n_sum), time=TimeSettings(0, 300)),
            update=PhaseSettings(prob=0.5, count=CountSettings(n_update, n_update), time=TimeSettings(0, 300)),
            sum2=Sum2Settings(count=CountSettings(n_sum, n_sum), time=TimeSettings(0, 300)),
        )
    )
    settings.model.length = model_len
    info, started = {}, threading.Event()

    def run():
        async def amain():
            store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
            machine, tx, events = await StateMachineInitializer(settings, store).init()
            rest = RestServer(Fetcher(events), PetMessageHandler(events, tx))
            host, port = await rest.start("127.0.0.1", 0)
            info["url"] = f"http://{host}:{port}"
            started.set()
            await machine.run()

        asyncio.run(amain())

    threading.Thread(target=run, daemon=True).start()
    started.wait(10)
    url = info["url"]
    probe = HttpClient(url)

    def sync(coro):
        return asyncio.run(coro)

    shared_step = lstm.make_train_step(hidden=hidden, learning_rate=args.lr)
    threads = []
    last_seed = None
    for round_no in range(1, args.rounds + 1):
        t0 = time.time()
        params = sync(probe.get_round_params())
        while last_seed is not None and params.seed.as_bytes() == last_seed:
            time.sleep(0.2)
            params = sync(probe.get_round_params())
        seed = params.seed.as_bytes()

        def kwargs(i):
            return dict(
                init_params_fn=lambda: lstm.init_params(
                    jax.random.PRNGKey(1), seq_len=seq_len, hidden=hidden
                ),
                make_step=lambda: shared_step,
                data=synthetic_shards(i, seq_len=seq_len),
                epochs=args.epochs,
                batch_size=16,
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
        # the federated average must at least fit the participating shards
        require_loss_improved(
            model_obj,
            template,
            lstm.init_params(jax.random.PRNGKey(1), seq_len=seq_len, hidden=hidden),
            model,
            [synthetic_shards(i, seq_len=seq_len) for i in range(n_update)],
        )


if __name__ == "__main__":
    main()
