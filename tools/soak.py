"""Long-haul soak: a real coordinator process under participant churn.

Runs the coordinator as a subprocess (the production entry point), then
cycles fresh participants through rounds over the REST socket — every round
gets NEW keypairs (churn), so dictionaries, multipart buffers and the model
archive are exercised continuously. Tracks the coordinator's RSS across
rounds; steady-state growth beyond the expected per-round model archive
indicates a leak.

Usage:
  python tools/soak.py --rounds 200 [--model-len 2000]
Prints one JSON line: rounds completed, wall, rounds/s, RSS start/end/slope.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rss_kb(pid: int) -> int:
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


def _scrape_console(port: int, require_tenants: list[str] | None = None) -> dict:
    """GET /statusz + /alerts off the live coordinator (DESIGN §20 smoke).

    Runs while the coordinator is still up — asserts the operator console
    renders (200, HTML, every tenant id present) and the SLO alert payload
    parses, and folds both into the soak's result JSON so CI carries the
    evidence."""
    from urllib.request import urlopen

    with urlopen(f"http://127.0.0.1:{port}/statusz", timeout=10) as resp:
        page = resp.read().decode("utf-8", "replace")
        if resp.status != 200 or "<html" not in page:
            raise RuntimeError(f"/statusz not healthy: {resp.status}")
    missing = [tid for tid in (require_tenants or []) if tid not in page]
    if missing:
        raise RuntimeError(f"/statusz missing tenants: {missing}")
    with urlopen(f"http://127.0.0.1:{port}/alerts", timeout=10) as resp:
        if resp.status != 200:
            raise RuntimeError(f"/alerts not healthy: {resp.status}")
        alerts = json.loads(resp.read())
    # per-tenant SLO burn gauges off /metrics: the soak's evidence that the
    # engine tracks tenants independently, not one merged series
    with urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
        text = resp.read().decode("utf-8", "replace")
    burn_tenants = sorted(
        {
            line.split('tenant="', 1)[1].split('"', 1)[0]
            for line in text.splitlines()
            if line.startswith("xaynet_slo_burn_rate{")
        }
    )
    return {
        "statusz_bytes": len(page),
        "alerts_active": alerts.get("active", []),
        "alerts_recent": len(alerts.get("recent", [])),
        "slo_burn_tenants": burn_tenants,
    }


CONFIG = """
[api]
bind_address = "127.0.0.1:{port}"

[pet.sum]
prob = 0.5
[pet.sum.count]
min = 1
max = 1
[pet.sum.time]
min = 0.0
max = 20.0

[pet.update]
prob = 0.9
[pet.update.count]
min = {update_min}
max = {update_max}
{update_quorum_line}
[pet.update.time]
min = 0.0
max = 20.0

[liveness]
stall_grace_s = {stall_grace}

[pet.sum2.count]
min = 1
max = 1
[pet.sum2.time]
min = 0.0
max = 20.0

[model]
length = {model_len}

[aggregation]
device = {agg_device}
batch_size = {agg_batch}
kernel = "{agg_kernel}"
wire_ingest = {agg_wire_ingest}

[storage]
backend = "filesystem"
model_dir = "{model_dir}"

{edge_enabled_line}
[log]
# info: the soak artifact reads the aggregator's "kernel resolved" line
filter = "info"
"""

EDGE_CONFIG = """
[api]
bind_address = "127.0.0.1:{port}"

[edge]
upstream_url = "http://127.0.0.1:{upstream_port}"
edge_id = "{edge_id}"
max_members = {max_members}
linger_s = 0.2
poll_s = 0.1

[log]
filter = "info"
"""


N_CHAOS_UPDATERS = 6

# per-tenant mask-config/model-size diversity for --tenants N: tenant i
# gets MODEL_LENS[i % ...] params and GROUPS[i % ...] group arithmetic, so
# the multi-tenant smoke genuinely packs variable-length models with
# different group orders into one pool (docs/DESIGN.md §19)
TENANT_MODEL_LENS = (1500, 2200, 900, 3000)
TENANT_GROUPS = ("integer", "prime", "power2", "integer")


def _tenant_config(port: int, model_len: int, group: str, model_dir: str) -> str:
    """One tenant's FULL override settings file (loaded standalone by the
    multi-tenant runner; [api] is unused there — the process listener comes
    from the base config)."""
    base = CONFIG.format(
        port=port,
        model_len=model_len,
        model_dir=model_dir,
        agg_device="true",
        agg_wire_ingest="false",
        agg_batch=2,
        agg_kernel="auto",
        update_min=3,
        update_max=3,
        update_quorum_line="",
        stall_grace=1.0,
        edge_enabled_line="",
    )
    return base + f'\n[mask]\ngroup_type = "{group}"\n'


def _drive_tenant_rounds(
    url: str, rounds: int, model_len: int, expected: bytes | None, label: str,
    round_timeout_s: float = 120.0,
) -> bytes:
    """Drive ``rounds`` PET rounds against ``url`` (a bare or /t/<tenant>
    base) with DETERMINISTIC participant models; every completed round's
    global model must equal ``expected`` (byte-identity vs the
    single-tenant control) when given. Returns the last model bytes.

    Each round gets ``round_timeout_s`` of wall clock — a tick-count bound
    would burn out in seconds once every participant is awaiting, racing
    the coordinator's first-round unmask compile."""
    from fractions import Fraction

    import numpy as np

    from xaynet_tpu.sdk.client import HttpClient
    from xaynet_tpu.sdk.participant import Participant
    from xaynet_tpu.sdk.simulation import keys_for_task

    def fetch_params():
        return asyncio.run(HttpClient(url, keep_alive=False).get_round_params())

    def fetch_model() -> bytes:
        model = asyncio.run(HttpClient(url, keep_alive=False).get_model())
        return np.asarray(model, dtype=np.float64).tobytes()

    completed = 0
    last_seed = None
    model_bytes = b""
    while completed < rounds:
        params = fetch_params()
        if params.seed.as_bytes() == last_seed:
            time.sleep(0.01)
            continue
        last_seed = params.seed.as_bytes()
        seed = last_seed
        summer = keys_for_task(seed, params.sum, params.update, "sum")
        upd, start = [], 0
        while len(upd) < 3:
            k = keys_for_task(seed, params.sum, params.update, "update", start=start)
            start += 100000
            if all(k.public != u.public for u in upd) and k.public != summer.public:
                upd.append(k)
        parts = [Participant(url, keys=summer, scalar=Fraction(1, 3))]
        for i, k in enumerate(upd):
            p = Participant(url, keys=k, scalar=Fraction(1, 3))
            p.set_model(np.full(model_len, 0.25 * (i + 1), dtype=np.float32))
            parts.append(p)
        deadline = time.time() + round_timeout_s
        closed = False
        while time.time() < deadline:
            for p in parts:
                p.tick()
            if fetch_params().seed.as_bytes() != seed:
                closed = True
                break
        if not closed:
            raise RuntimeError(f"{label}: round {completed + 1} did not complete")
        model_bytes = fetch_model()
        if expected is not None and model_bytes != expected:
            raise RuntimeError(
                f"{label}: round {completed + 1} NOT byte-identical to the "
                "single-tenant control"
            )
        completed += 1
    return model_bytes


def run_multi_tenant_soak(args) -> None:
    """--tenants N: N tenants with distinct mask configs/model sizes in ONE
    coordinator process, each driven concurrently over /t/<tenant>/... and
    checked byte-identical to its single-tenant control run."""
    import socket
    import threading

    n = args.tenants
    tenants = [f"t{i}" for i in range(n)]
    spec = {
        tid: (
            TENANT_MODEL_LENS[i % len(TENANT_MODEL_LENS)],
            TENANT_GROUPS[i % len(TENANT_GROUPS)],
        )
        for i, tid in enumerate(tenants)
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

    def wait_listening(port: int, proc) -> None:
        deadline = time.time() + 90
        while time.time() < deadline:
            try:
                with socket.create_connection(("127.0.0.1", port), timeout=1):
                    return
            except OSError:
                if proc.poll() is not None:
                    raise RuntimeError("coordinator exited during startup")
                time.sleep(0.25)
        raise RuntimeError("coordinator did not start listening in 90s")

    t0 = time.perf_counter()
    controls: dict[str, bytes] = {}
    with tempfile.TemporaryDirectory() as tmp:
        cfg_dir = os.path.join(tmp, "tenants")
        os.makedirs(cfg_dir)
        for tid, (mlen, group) in spec.items():
            with open(os.path.join(cfg_dir, f"{tid}.toml"), "w") as f:
                f.write(
                    _tenant_config(
                        args.port, mlen, group, os.path.join(tmp, f"models-{tid}")
                    )
                )
        # --- single-tenant control runs: one round each, alone ------------
        for tid, (mlen, group) in spec.items():
            log = open(os.path.join(tmp, f"control-{tid}.log"), "w")
            proc = subprocess.Popen(
                [sys.executable, "-m", "xaynet_tpu.server.runner",
                 "-c", os.path.join(cfg_dir, f"{tid}.toml")],
                env=env, stdout=log, stderr=subprocess.STDOUT,
            )
            try:
                wait_listening(args.port, proc)
                controls[tid] = _drive_tenant_rounds(
                    f"http://127.0.0.1:{args.port}", 1, mlen, None,
                    f"control {tid}",
                )
            finally:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=5)
                log.close()
            print(f"control {tid}: model {len(controls[tid])} bytes", file=sys.stderr)
        # --- the multi-tenant run -----------------------------------------
        base_cfg = os.path.join(tmp, "multi.toml")
        with open(base_cfg, "w") as f:
            f.write(
                _tenant_config(
                    args.port,
                    spec[tenants[0]][0],
                    spec[tenants[0]][1],
                    os.path.join(tmp, "models-multi"),
                )
                + "\n[tenancy]\nenabled = true\n"
                + f'tenants = "{",".join(tenants)}"\n'
                + f'config_dir = "{cfg_dir}"\n'
            )
        log_path = os.path.join(tmp, "multi.log")
        log = open(log_path, "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", "xaynet_tpu.server.runner", "-c", base_cfg],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )
        try:
            wait_listening(args.port, proc)
            errors: list[BaseException] = []

            def drive(tid: str) -> None:
                mlen, _ = spec[tid]
                try:
                    _drive_tenant_rounds(
                        f"http://127.0.0.1:{args.port}/t/{tid}",
                        args.rounds,
                        mlen,
                        controls[tid],
                        f"tenant {tid}",
                    )
                except BaseException as err:
                    errors.append(err)

            threads = [
                threading.Thread(target=drive, args=(tid,), daemon=True)
                for tid in tenants
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            if errors:
                raise errors[0]
            console = _scrape_console(args.port, require_tenants=tenants)
            rss = _rss_kb(proc.pid)
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5)
            log.close()
    print(
        json.dumps(
            {
                "tenants": {
                    tid: {"model_len": spec[tid][0], "group": spec[tid][1]}
                    for tid in tenants
                },
                "rounds_per_tenant": args.rounds,
                "byte_identical": True,
                "wall_s": round(time.perf_counter() - t0, 2),
                "rss_kb": rss,
                "console": console,
            }
        )
    )


def _http_status(url: str, method: str = "GET", body: bytes | None = None,
                 headers: dict | None = None, timeout: float = 60.0):
    """One HTTP call returning (status, body bytes) — 4xx/5xx included
    (urllib raises on those; the churn soak ASSERTS on 401/404/429)."""
    from urllib.error import HTTPError
    from urllib.request import Request, urlopen

    req = Request(url, data=body, method=method)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except HTTPError as err:
        return err.code, err.read()


def _metric_value(port: int, family: str, labels: dict) -> float | None:
    """One sample off the live /metrics endpoint (Prometheus text)."""
    from urllib.request import urlopen

    with urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
        text = resp.read().decode("utf-8", "replace")
    for line in text.splitlines():
        if not line.startswith(family + "{") and line.split(" ")[0] != family:
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                return None
    return None


def _metric_sum(port: int, family: str) -> float:
    """Sum of every sample of ``family`` across all label sets (e.g. the
    total leased pool pages over every arena x tenant)."""
    from urllib.request import urlopen

    with urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
        text = resp.read().decode("utf-8", "replace")
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(family + "{") and line.split(" ")[0] != family:
            continue
        try:
            total += float(line.rsplit(" ", 1)[1])
        except ValueError:
            continue
    return total


# --- SIGKILL-matrix chaos soak (docs/DESIGN.md §9) --------------------------

# the full matrix: one seeded kill coordinate per phase family plus the
# publish window. <site>:<n> dies on the n-th visit to the site — "update:2"
# is mid-window (2 of 3 updates journaled), "unmask:publish:1" lands AFTER
# the model save but BEFORE the journal retires (the idempotent-republish
# window, the nastiest restart point). "journal:sections:2" is the file
# store's own point, inside a journal write: the second entry that carries an
# aggregate has its sections on disk and its head not yet renamed into place,
# so the restart must find the entry before it (the phase it resumes into is
# read from that entry).
KILL_MATRIX = ("sum:1", "update:2", "journal:sections:2", "sum2:1", "unmask:publish:1")
KILL_SEED = 46  # the participants' weights and mask seeds (benchmark/harness/reference.py)
KILL_CONCURRENCY = 8  # uploads in flight, as the benchmark's flood8
KILL_SAMPLE = (1_000_000, 1024)  # positions compared with the plain reference, and each edge


def _kill_spec(args) -> dict:
    """The deployment the matrix kills: vector, mask, fold batch, round."""
    if args.mask:
        names = args.mask.lower().split("/")
        if len(names) != 4:
            raise SystemExit("--mask is GROUP/DATA/BOUND/MODEL, e.g. integer/f32/b0/m6")
        mask = dict(zip(("group_type", "data_type", "bound_type", "model_type"), names))
    else:
        from xaynet_tpu.server.settings import MaskSettings

        shipped = MaskSettings()
        mask = {key: getattr(shipped, key).name.lower()
                for key in ("group_type", "data_type", "bound_type", "model_type")}
    n = args.updates
    if n % args.batch_size:
        raise SystemExit(f"--updates {n} is not whole fold batches of {args.batch_size}")
    den = 1
    while den < n:
        den *= 2  # a dyadic scalar: exact in the SDK's encode and in the reference
    at_size = args.model_len > 1_000_000
    return {"model_len": args.model_len, "mask": mask, "batch_size": args.batch_size,
            "updates": n, "scalar_den": den,
            # a phase window no upload of the size can outlast; a start; a round
            "time_max": 900.0 if at_size else 20.0,
            "boot_s": 600 if at_size else 90,
            "round_s": 1500.0 if at_size else 300.0}


def _healthz(url: str) -> dict:
    from urllib.request import urlopen

    with urlopen(url + "/healthz", timeout=30) as resp:
        return json.loads(resp.read())


def _kill_config(port: int, spec: dict, state_dir: str) -> str:
    """A checkpoint-enabled coordinator config whose durable state (file
    coordinator + model archive + round journal) all lives under
    ``state_dir`` — the restart boots on the SAME tree the kill orphaned.

    ``checkpoint_every_batches = 1`` puts a journal write after every fold
    batch, before the acknowledgement of the upload that filled it (with the
    default ``--batch-size 1``: before every acknowledgement); what the
    benchmark's ``resnet50-f32m6-durable`` sets under ``toml`` is set here."""
    base = CONFIG.format(
        port=port,
        model_len=spec["model_len"],
        model_dir=state_dir,
        agg_device="true",
        agg_wire_ingest="false",
        agg_batch=spec["batch_size"],
        agg_kernel="auto",
        update_min=spec["updates"],
        update_max=spec["updates"],
        update_quorum_line="",
        stall_grace=5.0,
        edge_enabled_line="",
    ).replace("max = 20.0", f"max = {spec['time_max']}")
    # the template's [storage] table already exists — inject the coordinator
    # backend into it (tomllib rejects a duplicate [storage] section)
    base = base.replace(
        'backend = "filesystem"', 'backend = "filesystem"\ncoordinator = "file"'
    )
    mask = "".join(f'{key} = "{value}"\n' for key, value in spec["mask"].items())
    return base + (
        f"\n[mask]\n{mask}"
        "\n[restore]\nenable = true\n"
        "\n[resilience]\n"
        "checkpoint_enabled = true\n"
        "checkpoint_every_batches = 1\n"
        "max_resume_attempts = 3\n"
    )


def _journal_header(state_dir: str) -> dict:
    """The header of the journal entry on disk, {} if there is none (the
    head file alone is read: the sections beside it can be of the model's
    size)."""
    import struct

    from xaynet_tpu.storage.memory import FileCoordinatorStorage

    try:
        found = FileCoordinatorStorage(
            os.path.join(state_dir, "coordinator_state.json"))._read_head()
        head = found[0]
        (hlen,) = struct.unpack_from("<I", head, 7)  # behind the magic
        return json.loads(head[11 : 11 + hlen])
    except (OSError, TypeError, ValueError, struct.error):
        return {}


def _journal_update_pks(state_dir: str) -> set:
    """The update participants the journal on disk holds."""
    return {bytes.fromhex(pk) for pk in _journal_header(state_dir).get("seed_dicts") or {}}


def _drive_crash_round(url: str, spec: dict, state_dir: str, label: str,
                       timeout_s: float) -> tuple[bytes, dict]:
    """Drive ONE deterministic PET round from the clients' side, through a
    coordinator's death and restart: the SDK's sum participant, and
    ``spec["updates"]`` uploads masked and sealed by the benchmark's forge
    (weights and mask seeds from ``KILL_SEED``, so every round of the matrix
    sends the same weights and must publish the same bytes) over
    ``KILL_CONCURRENCY`` connections. Every fetch retries through the
    dead-socket window and ``Participant.tick`` swallows transport errors.
    A silo sends again whatever the journal on disk does not hold once the
    coordinator serves again: the upload that died with its request, and
    the ones answered 200 since the last entry. Returns the published model's
    bytes and its comparison with the plain reference."""
    import numpy as np

    from benchmark.harness import forge as forge_mod, reference
    from xaynet_tpu.sdk.client import HttpClient
    from xaynet_tpu.sdk.participant import Participant
    from xaynet_tpu.sdk.simulation import keys_for_task

    n, model_len, den = spec["updates"], spec["model_len"], spec["scalar_den"]
    deadline = time.time() + timeout_s

    def retry(call, what: str):
        while True:
            if time.time() > deadline:
                raise RuntimeError(f"{label}: {what} not before timeout")
            try:
                value = call()
                if value is not None:
                    return value
            except Exception:
                pass  # coordinator dead or restarting
            time.sleep(0.2)

    def probe(call):
        client = HttpClient(url, keep_alive=False, timeout=600.0)
        try:
            return asyncio.run(call(client))
        finally:
            client.close()

    def health() -> dict:
        return _healthz(url)

    forge = forge_mod.Forge(
        seed=KILL_SEED, order=list(range(n)), scalar_den=den, model_length=model_len,
        mask=spec["mask"], workers=forge_mod.default_workers())
    summer = None
    try:
        params = retry(lambda: probe(lambda c: c.get_round_params()), "round parameters")
        seed = params.seed.as_bytes()
        round_id = retry(lambda: health()["round_id"], "/healthz")
        summer = Participant(
            HttpClient(url, timeout=600.0),
            keys=keys_for_task(seed, params.sum, params.update, "sum"),
            device_sum2=False, max_message_size=None)

        def settled(done) -> bool:
            try:
                return bool(done())
            except Exception:  # coordinator dead or restarting
                time.sleep(0.2)
                return False

        def tick_until(done, what: str) -> None:
            while not settled(done):
                if time.time() > deadline:
                    raise RuntimeError(f"{label}: {what} not before timeout")
                summer.tick()
                if not summer.made_progress():
                    time.sleep(0.05)

        tick_until(lambda: health()["phase"] == "update", "sum message accepted")
        sums = retry(lambda: probe(lambda c: c.get_sums()), "sum dictionary")
        sealed, pks = forge.seal(params.to_dict(), sums, list(range(n)))

        async def send(indices: list) -> set:
            client = HttpClient(url, timeout=600.0, max_idle=KILL_CONCURRENCY)
            gate, ok = asyncio.Semaphore(KILL_CONCURRENCY), set()

            async def one(i: int) -> None:
                async with gate:
                    try:
                        await client.send_message(sealed[i])
                        ok.add(i)
                    except Exception:
                        pass  # the coordinator died under it

            try:
                await asyncio.gather(*(one(i) for i in indices))
            finally:
                client.close()
            return ok

        pending, resent = list(range(n)), 0
        while pending:
            ok = asyncio.run(send(pending))
            if len(ok) == len(pending):
                break
            if time.time() > deadline:
                raise RuntimeError(f"{label}: uploads not answered before timeout")
            retry(lambda: health()["phase"], "coordinator serving again")
            held = _journal_update_pks(state_dir)
            pending = [i for i in range(n) if pks[i] not in held]
            resent += len(pending)
        tick_until(lambda: health()["round_id"] > round_id, "global model published")
        model = retry(lambda: probe(lambda c: c.get_model()), "global model")
    finally:
        if summer is not None:
            summer.close()
        forge.close()
    model = np.ascontiguousarray(model, dtype=np.float64)
    pair = forge_mod._mask_config(spec["mask"])
    add_shift, exp_shift = int(pair.vect.add_shift), int(pair.vect.exp_shift)
    positions = reference.sample_positions(KILL_SEED, model_len, *KILL_SAMPLE)
    ref, mean = reference.reference_model(
        KILL_SEED, list(range(n)), model_len, den, add_shift, exp_shift, positions)
    cmp = reference.compare(model, ref, positions, mean, den, exp_shift)
    cmp["resent"] = resent
    if cmp["mismatched_positions"] != 0:
        raise RuntimeError(f"{label}: model differs from the plain reference: {cmp}")
    return model.tobytes(), cmp


def run_kill_matrix_soak(args) -> None:
    """--kill-matrix: SIGKILL the coordinator at seeded (phase, message)
    coordinates, restart it on the same durable tree, and drive the
    surviving participants to completion. Per coordinate the harness
    asserts (docs/DESIGN.md §9):

    - the restarted coordinator RESUMED the killed phase from the round
      journal (``xaynet_resume_total{phase,outcome="resumed"}`` >= 1);
    - the published global model is byte-identical to an unkilled control
      and equal to the plain reference bit for bit;
    - zero pool pages and zero staging ring buffers stay leased after the
      round (no leak across a kill);
    - the restart-to-serving wall (``xaynet_recovery_seconds``) and the
      restart's ``/healthz`` ``startup`` timeline are recorded, in the JSON
      line the soak prints.

    The coordinator runs on the caller's platform (``JAX_PLATFORMS`` as the
    caller set it: unset on a chip host, ``cpu`` here), at the size the
    arguments give; this process and its forge workers stay on the CPU.
    """
    import signal
    import socket
    import threading

    spec = _kill_spec(args)
    coords = [
        c.strip()
        for c in (args.kill_points or ",".join(KILL_MATRIX)).split(",")
        if c.strip()
    ]
    env = dict(os.environ)
    env.pop("XAYNET_KILL_POINT", None)
    if env.get("JAX_PLATFORMS", "").strip() == "cpu":
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process, its forge workers, its sum participant
    boot_s, round_s = spec["boot_s"], spec["round_s"]

    def wait_listening(port: int, proc) -> None:
        deadline = time.time() + boot_s
        while time.time() < deadline:
            try:
                with socket.create_connection(("127.0.0.1", port), timeout=1):
                    return
            except OSError:
                if proc.poll() is not None:
                    raise RuntimeError("coordinator exited during startup")
                time.sleep(0.25)
        raise RuntimeError(f"coordinator did not start listening in {boot_s}s")

    def stop(proc) -> None:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=5)

    url = f"http://127.0.0.1:{args.port}"
    t0 = time.perf_counter()
    results = []
    with tempfile.TemporaryDirectory(dir=args.state_dir) as tmp:
        def boot(state_dir: str, tag: str, extra_env: dict | None = None):
            cfg = os.path.join(state_dir, "coordinator.toml")
            if not os.path.exists(cfg):
                with open(cfg, "w") as f:
                    f.write(_kill_config(args.port, spec, state_dir))
            log = open(os.path.join(state_dir, f"{tag}.log"), "w")
            proc = subprocess.Popen(
                [sys.executable, "-m", "xaynet_tpu.server.runner", "-c", cfg],
                env=dict(env, **(extra_env or {})),
                stdout=log, stderr=subprocess.STDOUT,
            )
            return proc, log

        # --- unkilled control: the byte-identity reference ----------------
        control_dir = os.path.join(tmp, "control")
        os.makedirs(control_dir)
        proc, log = boot(control_dir, "control")
        try:
            wait_listening(args.port, proc)
            control, cmp = _drive_crash_round(url, spec, control_dir, "control", round_s)
        finally:
            stop(proc)
            log.close()
        print(f"control: model {len(control)} bytes, {cmp}", file=sys.stderr)

        # --- the matrix ---------------------------------------------------
        for coord in coords:
            phase = coord.split(":", 1)[0]
            state_dir = os.path.join(tmp, coord.replace(":", "_"))
            os.makedirs(state_dir)
            proc, log = boot(state_dir, "killed", {"XAYNET_KILL_POINT": coord})
            box: dict = {}

            def drive() -> None:
                try:
                    box["model"], box["cmp"] = _drive_crash_round(
                        url, spec, state_dir, f"kill {coord}", 2 * round_s
                    )
                except BaseException as err:
                    box["error"] = err

            th = threading.Thread(target=drive, daemon=True)
            try:
                wait_listening(args.port, proc)
                th.start()
                # the seeded kill MUST fire: anything else (clean exit,
                # crash-on-boot, survived round) fails the matrix
                rc = proc.wait(timeout=round_s)
                if rc != -signal.SIGKILL:
                    raise RuntimeError(f"{coord}: coordinator exited {rc}, expected SIGKILL")
            finally:
                log.close()
            held = len(_journal_update_pks(state_dir))
            if phase == "journal":
                # killed inside a write: the entry before it is what the
                # restart finds, and its tag the phase it resumes into
                phase = _journal_header(state_dir).get("phase", "?")
            print(f"{coord}: killed (pid {proc.pid}), {held} updates in the journal",
                  file=sys.stderr)
            t_restart = time.perf_counter()
            proc, log = boot(state_dir, "restarted")
            try:
                wait_listening(args.port, proc)
                restart_wall = time.perf_counter() - t_restart
                startup = _healthz(url).get("startup")
                th.join(timeout=2 * round_s)
                if th.is_alive():
                    raise RuntimeError(f"{coord}: round did not complete after restart")
                if "error" in box:
                    raise box["error"]
                if box["model"] != control:
                    raise RuntimeError(f"{coord}: model NOT byte-identical to the unkilled control")
                resumed = _metric_value(
                    args.port, "xaynet_resume_total",
                    {"phase": phase, "outcome": "resumed"},
                )
                if not resumed:
                    raise RuntimeError(
                        f"{coord}: no xaynet_resume_total{{phase={phase!r},"
                        f'outcome="resumed"}} sample after restart'
                    )
                recovery_s = _metric_value(args.port, "xaynet_recovery_seconds", {})
                leaked = _metric_sum(args.port, "xaynet_pool_pages")
                if leaked:
                    raise RuntimeError(f"{coord}: {leaked:g} pool pages leaked")
                ring = _metric_sum(args.port, "xaynet_streaming_staging_depth") + _metric_sum(
                    args.port, "xaynet_streaming_shard_staging_depth")
                if ring:
                    raise RuntimeError(f"{coord}: {ring:g} staging ring buffers still leased")
                journal = _healthz(url).get("journal")
            finally:
                stop(proc)
                log.close()
            print(
                f"{coord}: resumed={resumed:g} recovery={recovery_s}s "
                f"restart_wall={restart_wall:.2f}s",
                file=sys.stderr,
            )
            results.append(
                {
                    "kill_point": coord,
                    "phase": phase,
                    "journalled_at_kill": held,
                    "resent": box["cmp"]["resent"],
                    "resumed": resumed,
                    "recovery_s": recovery_s,
                    "restart_to_serving_s": round(restart_wall, 3),
                    "startup": startup,
                    "byte_identical": True,
                    "mismatched_positions": box["cmp"]["mismatched_positions"],
                    "positions_compared": box["cmp"]["positions_compared"],
                    "pool_pages_leaked": leaked,
                    "ring_buffers_leased": ring,
                    "journal": journal,
                }
            )
    print(
        json.dumps(
            {
                "kill_matrix": results,
                "model_len": spec["model_len"],
                "mask": "/".join(spec["mask"].values()),
                "batch_size": spec["batch_size"],
                "updates": spec["updates"],
                "platform": env.get("JAX_PLATFORMS") or "default",
                "byte_identical": True,
                "wall_s": round(time.perf_counter() - t0, 2),
            }
        )
    )


def run_tenant_churn_soak(args) -> None:
    """--tenant-churn: the elastic-lifecycle chaos soak (docs/DESIGN.md §23).

    One multi-tenant coordinator boots with t0+t1; t1's storage is
    fault-injected (``t:t1:...`` sites) so its rounds fail and trip the
    quarantine, while t0 drives rounds CONTINUOUSLY — every one
    byte-identical to its single-tenant control. Mid-run, t2 is onboarded
    over the authenticated /admin/tenants API, completes a
    control-identical round, and is drained back out; the soak then pins:
    quarantined t1 sheds with 429 and auto-readmits via the half-open
    probe round, admin auth rejects bad tokens, the drained tenant's
    routes 404, and its pool pages are ZERO after teardown."""
    import socket
    import threading

    # t2 stays on the integer group: its round is driven ONCE against a
    # wall-clock-bounded driver mid-churn, and the power2 group's slow
    # big-int unmask can outrun that budget on a loaded CI host
    spec = {
        "t0": (TENANT_MODEL_LENS[0], TENANT_GROUPS[0]),
        "t1": (TENANT_MODEL_LENS[1], TENANT_GROUPS[1]),
        "t2": (900, "integer"),
    }
    admin_token = "churn-soak-admin-token"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    # fault ONE tenant's storage: t1's Idle delete_dicts eats the whole
    # 4-attempt retry budget on rounds 1 AND 2 (max=8 faults), so exactly
    # two rounds fail — the lifecycle quarantine threshold below. The
    # budget is then SPENT: the half-open probe round's storage works and
    # t1 earns its way back in. is_ready is NOT faulted (readiness checks
    # stay truthful, and their recorded successes reset the storage
    # breaker between rounds — the STORAGE breaker never opens; only the
    # lifecycle quarantine does).
    env["XAYNET_FAULT_PLAN"] = (
        "seed=11;t:t1:storage.coordinator.delete_dicts:error,rate=1.0,max=8"
    )

    def wait_listening(port: int, proc) -> None:
        deadline = time.time() + 90
        while time.time() < deadline:
            try:
                with socket.create_connection(("127.0.0.1", port), timeout=1):
                    return
            except OSError:
                if proc.poll() is not None:
                    raise RuntimeError("coordinator exited during startup")
                time.sleep(0.25)
        raise RuntimeError("coordinator did not start listening in 90s")

    def stop(proc) -> None:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=5)

    t0_wall = time.perf_counter()
    controls: dict[str, bytes] = {}
    events: list[str] = []
    with tempfile.TemporaryDirectory() as tmp:
        cfg_dir = os.path.join(tmp, "tenants")
        os.makedirs(cfg_dir)
        for tid, (mlen, group) in spec.items():
            with open(os.path.join(cfg_dir, f"{tid}.toml"), "w") as f:
                f.write(
                    _tenant_config(
                        args.port, mlen, group, os.path.join(tmp, f"models-{tid}")
                    )
                )
        # --- single-tenant control runs (fault plan OFF) -------------------
        control_env = {k: v for k, v in env.items() if k != "XAYNET_FAULT_PLAN"}
        for tid, (mlen, group) in spec.items():
            clog_path = os.path.join(tmp, f"control-{tid}.log")
            log = open(clog_path, "w")
            proc = subprocess.Popen(
                [sys.executable, "-m", "xaynet_tpu.server.runner",
                 "-c", os.path.join(cfg_dir, f"{tid}.toml")],
                env=control_env, stdout=log, stderr=subprocess.STDOUT,
            )
            try:
                wait_listening(args.port, proc)
                controls[tid] = _drive_tenant_rounds(
                    f"http://127.0.0.1:{args.port}", 1, mlen, None, f"control {tid}"
                )
            except BaseException:
                log.flush()
                with open(clog_path) as lf:
                    print("".join(lf.readlines()[-40:]), file=sys.stderr)
                raise
            finally:
                stop(proc)
                log.close()
            print(f"control {tid}: model {len(controls[tid])} bytes", file=sys.stderr)
        # --- the churn run: boot with t0 + t1, t2 arrives later ------------
        base_cfg = os.path.join(tmp, "multi.toml")
        with open(base_cfg, "w") as f:
            f.write(
                _tenant_config(
                    args.port, spec["t0"][0], spec["t0"][1],
                    os.path.join(tmp, "models-multi"),
                )
                + "\n[tenancy]\nenabled = true\n"
                + 'tenants = "t0,t1"\n'
                + f'config_dir = "{cfg_dir}"\n'
                + f'admin_token = "{admin_token}"\n'
                + "drain_timeout_s = 60.0\n"
                + "quarantine_failures = 2\n"
                + "quarantine_reset_s = 5.0\n"
            )
        log_path = os.path.join(tmp, "multi.log")
        log = open(log_path, "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", "xaynet_tpu.server.runner", "-c", base_cfg],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )
        base = f"http://127.0.0.1:{args.port}"
        try:
            wait_listening(args.port, proc)
            # -- t0: continuous control-identical rounds, the whole time ----
            stop_t0 = threading.Event()
            t0_rounds = [0]
            t0_errors: list[BaseException] = []

            def drive_t0() -> None:
                try:
                    while not stop_t0.is_set():
                        _drive_tenant_rounds(
                            f"{base}/t/t0", 1, spec["t0"][0], controls["t0"],
                            "tenant t0",
                        )
                        t0_rounds[0] += 1
                except BaseException as err:
                    t0_errors.append(err)

            t0_thread = threading.Thread(target=drive_t0, daemon=True)
            t0_thread.start()

            # -- t1 trips the quarantine under storage faults ---------------
            deadline = time.time() + 120
            while time.time() < deadline:
                if _metric_value(args.port, "xaynet_tenant_state", {"tenant": "t1"}) == 3.0:
                    break
                time.sleep(0.1)
            else:
                raise RuntimeError("t1 never reached the quarantined state")
            events.append("t1 quarantined")
            # quarantined ingress sheds with 429 + Retry-After
            status, _ = _http_status(
                f"{base}/t/t1/message", method="POST", body=b"probe", timeout=10
            )
            if status != 429:
                raise RuntimeError(f"quarantined POST expected 429, got {status}")
            events.append("t1 sheds 429")
            if _metric_value(args.port, "xaynet_tenant_quarantines_total",
                             {"tenant": "t1"}) != 1.0:
                raise RuntimeError("xaynet_tenant_quarantines_total{t1} != 1")

            # -- auto-readmission: the half-open probe round completes ------
            probe_deadline = time.time() + 180
            readmitted = False
            while time.time() < probe_deadline:
                try:
                    _drive_tenant_rounds(
                        f"{base}/t/t1", 1, spec["t1"][0], controls["t1"],
                        "tenant t1 probe", round_timeout_s=25.0,
                    )
                    readmitted = True
                    break
                except Exception:
                    time.sleep(0.5)
            if not readmitted:
                raise RuntimeError("t1 probe round never completed (no readmission)")
            state_deadline = time.time() + 30
            while time.time() < state_deadline:
                if _metric_value(args.port, "xaynet_tenant_state", {"tenant": "t1"}) == 2.0:
                    break
                time.sleep(0.1)
            else:
                raise RuntimeError("t1 not back to serving after the probe round")
            events.append("t1 readmitted (control-identical probe round)")

            # -- admin auth: constant-time token, bad/missing -> 401 --------
            for hdrs in ({}, {"x-admin-token": "wrong"}):
                status, _ = _http_status(
                    f"{base}/admin/tenants", headers=hdrs, timeout=10
                )
                if status != 401:
                    raise RuntimeError(f"admin without valid token: got {status}")
            events.append("admin auth rejects bad tokens")

            # -- onboard t2 mid-run over the admin API ----------------------
            status, body = _http_status(
                f"{base}/admin/tenants", method="POST",
                body=json.dumps({"tenant": "t2"}).encode(),
                headers={"x-admin-token": admin_token,
                         "content-type": "application/json"},
                timeout=180,
            )
            if status != 200:
                raise RuntimeError(f"onboard t2 failed: {status} {body[:200]!r}")
            onboard_s = json.loads(body).get("onboard_s")
            _drive_tenant_rounds(
                f"{base}/t/t2", 1, spec["t2"][0], controls["t2"], "tenant t2"
            )
            events.append(f"t2 onboarded ({onboard_s}s) + control-identical round")

            # -- drain t2 back out; zero leaked pages, routes 404 -----------
            status, body = _http_status(
                f"{base}/admin/tenants/t2", method="DELETE",
                headers={"x-admin-token": admin_token}, timeout=120,
            )
            if status != 200:
                raise RuntimeError(f"offboard t2 failed: {status} {body[:200]!r}")
            outcome = json.loads(body).get("outcome")
            pages = _metric_value(
                args.port, "xaynet_pool_pages", {"arena": "host", "tenant": "t2"}
            )
            if pages not in (None, 0.0):
                raise RuntimeError(f"t2 leaked {pages} host pool pages after drain")
            status, _ = _http_status(f"{base}/t/t2/params", timeout=10)
            if status != 404:
                raise RuntimeError(f"drained t2 routes expected 404, got {status}")
            events.append(f"t2 drained ({outcome}); zero leaked pages; routes 404")

            # -- t0 survived the whole churn, byte-identical throughout -----
            stop_t0.set()
            t0_thread.join(timeout=300)
            if t0_errors:
                raise t0_errors[0]
            if t0_rounds[0] < 1:
                raise RuntimeError("t0 completed no rounds during the churn")
            console = _scrape_console(args.port, require_tenants=["t0", "t1"])
            rss = _rss_kb(proc.pid)
        except BaseException:
            log.flush()
            with open(log_path) as lf:
                tail = lf.readlines()[-60:]
            print("".join(tail), file=sys.stderr)
            raise
        finally:
            stop(proc)
            log.close()
    print(
        json.dumps(
            {
                "churn_events": events,
                "t0_rounds_byte_identical": t0_rounds[0],
                "wall_s": round(time.perf_counter() - t0_wall, 2),
                "rss_kb": rss,
                "console": console,
            }
        )
    )


def run_chaos_soak_sync(
    port: int, rounds: int, model_len: int, dropout: float, stragglers: int
) -> dict:
    """Churn soak: the sum leg runs a real Participant; the update leg is
    driven by ``flood`` with the dropout/straggler knobs, so every round
    exercises the quorum-completion (degraded close) path end to end over
    the REST socket. Returns per-run churn totals alongside the round
    count."""
    from fractions import Fraction

    import numpy as np

    from xaynet_tpu.sdk.client import HttpClient, ResilientClient
    from xaynet_tpu.sdk.participant import Participant
    from xaynet_tpu.sdk.simulation import flood, keys_for_task

    url = f"http://127.0.0.1:{port}"

    def _client(round_seed: bytes | None = None):
        # a multi-hundred-round soak must survive the transient blips it
        # exists to exercise: one connection reset on a bare HttpClient
        # would abort the whole run (the sum leg already retries — the
        # Participant wraps its client in ResilientClient by default)
        # one-shot per-poll client: its event loop dies with asyncio.run,
        # so a pooled keep-alive socket would just leak until GC
        client = ResilientClient(HttpClient(url, keep_alive=False))
        # pin the round's trace id: chaos uploads stitch into the
        # coordinator's round trace, so a failed round's flight dump can
        # be joined to the soak's own logs
        client.set_round_trace(round_seed)
        return client

    def fetch_params():
        return asyncio.run(_client().get_round_params())

    completed = 0
    dropped_total = straggled_total = accepted_total = 0
    last_seed = None
    t0 = time.perf_counter()
    while completed < rounds:
        params = fetch_params()
        if params.seed.as_bytes() == last_seed:
            time.sleep(0.01)
            continue
        last_seed = params.seed.as_bytes()
        seed = last_seed
        summer = Participant(
            url,
            keys=keys_for_task(seed, params.sum, params.update, "sum"),
            scalar=Fraction(1, N_CHAOS_UPDATERS),
        )
        # drive the summer through Sum so the sum dictionary exists
        for _ in range(200):
            summer.tick()
            sum_dict = asyncio.run(_client().get_sums())
            if sum_dict:
                break
            time.sleep(0.05)
        else:
            raise RuntimeError(f"round {completed + 1}: sum dictionary never appeared")

        async def flood_updates():
            client = _client(round_seed=seed)

            async def submit(blob: bytes) -> None:
                await client.send_message(blob)

            rng = np.random.default_rng(completed + 1)
            return await flood(
                submit,
                params,
                sum_dict,
                N_CHAOS_UPDATERS,
                models=[
                    rng.uniform(-1, 1, model_len).astype(np.float32)
                    for _ in range(N_CHAOS_UPDATERS)
                ],
                scalar=Fraction(1, N_CHAOS_UPDATERS),
                key_spacing=100_000,
                dropout_rate=dropout,
                stragglers=stragglers,
                straggle_delay_s=0.3,
                churn_seed=completed + 1,
            )

        stats = asyncio.run(flood_updates())
        dropped_total += stats.dropped
        straggled_total += stats.straggled
        accepted_total += stats.accepted
        # the summer finishes sum2 and the round closes (degraded when the
        # dropouts left the window below count.min)
        try:
            for _ in range(400):
                summer.tick()
                if fetch_params().seed.as_bytes() != seed:
                    break
                time.sleep(0.05)
            else:
                raise RuntimeError(f"round {completed + 1} did not complete")
        finally:
            summer.close()
        completed += 1
    return {
        "rounds": completed,
        "wall_s": round(time.perf_counter() - t0, 2),
        "updates_accepted": accepted_total,
        "updates_dropped": dropped_total,
        "updates_straggled": straggled_total,
    }


def run_two_tier_soak_sync(
    port: int, edge_ports: list, rounds: int, model_len: int, updaters: int
) -> dict:
    """Two-tier soak: the sum leg talks to the coordinator directly; every
    update upload goes to an EDGE (round-robin across ``edge_ports``),
    which folds windows locally and ships partial-aggregate envelopes
    upstream. The round completes exactly like the flat topology — the
    coordinator just sees envelopes instead of per-participant updates."""
    import itertools

    from fractions import Fraction

    import numpy as np

    from xaynet_tpu.sdk.client import HttpClient, ResilientClient
    from xaynet_tpu.sdk.participant import Participant
    from xaynet_tpu.sdk.simulation import flood, keys_for_task

    url = f"http://127.0.0.1:{port}"
    edge_urls = [f"http://127.0.0.1:{p}" for p in edge_ports]

    def fetch_params():
        # one-shot per-poll clients (here and below): the loop dies with
        # asyncio.run, so a pooled keep-alive socket would leak until GC
        return asyncio.run(
            ResilientClient(HttpClient(url, keep_alive=False)).get_round_params()
        )

    completed = 0
    accepted_total = 0
    last_seed = None
    t0 = time.perf_counter()
    while completed < rounds:
        params = fetch_params()
        if params.seed.as_bytes() == last_seed:
            time.sleep(0.01)
            continue
        last_seed = params.seed.as_bytes()
        seed = last_seed
        summer = Participant(
            url,
            keys=keys_for_task(seed, params.sum, params.update, "sum"),
            scalar=Fraction(1, updaters),
        )
        try:
            for _ in range(200):
                summer.tick()
                sum_dict = asyncio.run(
                    ResilientClient(HttpClient(url, keep_alive=False)).get_sums()
                )
                if sum_dict:
                    break
                time.sleep(0.05)
            else:
                raise RuntimeError(f"round {completed + 1}: sum dictionary never appeared")

            async def flood_edges():
                clients = [ResilientClient(HttpClient(u)) for u in edge_urls]
                for c in clients:
                    # two-tier uploads carry the round trace id too: the
                    # edge adopts it, so edge + coordinator + soak stitch
                    c.set_round_trace(seed)
                rr = itertools.count()

                async def submit(blob: bytes) -> None:
                    await clients[next(rr) % len(clients)].send_message(blob)

                rng = np.random.default_rng(completed + 1)
                try:
                    return await flood(
                        submit,
                        params,
                        sum_dict,
                        updaters,
                        models=[
                            rng.uniform(-1, 1, model_len).astype(np.float32)
                            for _ in range(updaters)
                        ],
                        scalar=Fraction(1, updaters),
                        key_spacing=100_000,
                    )
                finally:
                    for c in clients:
                        c.close()

            stats = asyncio.run(flood_edges())
            accepted_total += stats.accepted
            for _ in range(600):
                summer.tick()
                if fetch_params().seed.as_bytes() != seed:
                    break
                time.sleep(0.05)
            else:
                raise RuntimeError(f"round {completed + 1} did not complete")
        finally:
            summer.close()
        completed += 1
    return {
        "rounds": completed,
        "wall_s": round(time.perf_counter() - t0, 2),
        "updates_accepted": accepted_total,
        "edges": len(edge_urls),
        "updaters_per_round": updaters,
    }


def run_soak_sync(port: int, rounds: int, model_len: int) -> dict:
    # synchronous driver: Participant.tick() owns its own event loop, so
    # the soak loop must NOT run inside asyncio itself
    from fractions import Fraction

    import numpy as np

    from xaynet_tpu.sdk.client import HttpClient
    from xaynet_tpu.sdk.participant import Participant
    from xaynet_tpu.sdk.simulation import keys_for_task

    url = f"http://127.0.0.1:{port}"

    def fetch_params():
        return asyncio.run(HttpClient(url, keep_alive=False).get_round_params())

    completed = 0
    last_seed = None
    t0 = time.perf_counter()
    while completed < rounds:
        params = fetch_params()
        if params.seed.as_bytes() == last_seed:
            time.sleep(0.01)
            continue
        last_seed = params.seed.as_bytes()
        seed = last_seed
        # churn: brand-new participants every round
        summer = keys_for_task(seed, params.sum, params.update, "sum")
        upd, start = [], 0
        while len(upd) < 3:
            k = keys_for_task(seed, params.sum, params.update, "update", start=start)
            start += 100000
            if all(k.public != u.public for u in upd) and k.public != summer.public:
                upd.append(k)

        parts = [Participant(url, keys=summer, scalar=Fraction(1, 3))]
        for i, k in enumerate(upd):
            p = Participant(url, keys=k, scalar=Fraction(1, 3))
            p.set_model(np.full(model_len, 0.25 * (i + 1), dtype=np.float32))
            parts.append(p)
        for _ in range(400):
            for p in parts:
                p.tick()
            if fetch_params().seed.as_bytes() != seed:
                break  # round completed, coordinator moved on
        else:
            raise RuntimeError(f"round {completed + 1} did not complete")
        completed += 1
    return {"rounds": completed, "wall_s": round(time.perf_counter() - t0, 2)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--model-len", type=int, default=2000)
    ap.add_argument("--port", type=int, default=18439)
    ap.add_argument(
        "--device-kernel",
        default=None,
        # no bare "pallas": the soak pins the coordinator to the CPU backend,
        # where explicit Mosaic compilation cannot succeed (auto falls back)
        choices=["auto", "xla", "pallas-interpret"],
        help="run the coordinator with device aggregation on the virtual mesh using this fold kernel",
    )
    ap.add_argument(
        "--wire-ingest",
        action="store_true",
        help="with --device-kernel: lazy Update parse + device unpack/validity "
        "(aggregation.wire_ingest=true) — leak-checks the production "
        "device-ingest mode over many rounds",
    )
    ap.add_argument(
        "--dropout",
        type=float,
        default=None,
        metavar="RATE",
        help="churn soak: drive updates through flood() with this dropout "
        "fraction; the coordinator runs with a quorum'd update window and "
        "closes those rounds DEGRADED instead of timing out",
    )
    ap.add_argument(
        "--stragglers",
        type=int,
        default=None,
        metavar="N",
        help="churn soak: delay N of the surviving update uploads per round "
        "(they still land inside the stall grace window)",
    )
    ap.add_argument(
        "--edges",
        type=int,
        default=None,
        metavar="N",
        help="two-tier soak: spawn N edge aggregator processes; all update "
        "uploads go through the edges (round-robin) and reach the "
        "coordinator as partial-aggregate envelopes",
    )
    ap.add_argument(
        "--edge-updaters",
        type=int,
        default=10,
        metavar="M",
        help="with --edges: update participants PER EDGE per round "
        "(default 10; --edges 4 therefore drives 40 participants)",
    )
    ap.add_argument(
        "--tenants",
        type=int,
        default=None,
        metavar="N",
        help="multi-tenant soak: N tenants with distinct mask configs and "
        "model sizes in ONE coordinator process (device aggregation over "
        "the shared paged pool), each driven concurrently over "
        "/t/<tenant>/... and checked byte-identical to its single-tenant "
        "control run (docs/DESIGN.md §19)",
    )
    ap.add_argument(
        "--tenant-churn",
        action="store_true",
        help="elastic-lifecycle chaos soak: onboard/drain tenants mid-run "
        "over the authenticated /admin/tenants API while one tenant's "
        "storage is fault-injected into quarantine and back; surviving "
        "tenants stay byte-identical to their single-tenant controls and "
        "the drained tenant leaks zero pool pages (docs/DESIGN.md §23)",
    )
    ap.add_argument(
        "--kill-matrix",
        action="store_true",
        help="SIGKILL-matrix chaos soak: kill the coordinator at seeded "
        "(phase, message-index) coordinates, restart it on the same durable "
        "tree and drive the surviving participants to completion — the "
        "global model must be byte-identical to an unkilled control and "
        "equal to the plain reference bit for bit, the killed phase must "
        "RESUME from the round journal, and zero pool pages or ring buffers "
        "may stay leased (docs/DESIGN.md §9). The coordinator runs on the "
        "caller's platform (JAX_PLATFORMS as set: cpu here, unset on a chip "
        "host) and at the size given by --model-len, --mask, --batch-size "
        "and --updates; sites: sum, update, sum2:base, sum2, unmask:start, "
        "unmask:publish, journal:sections",
    )
    ap.add_argument(
        "--kill-points",
        default=None,
        metavar="SITE:N,...",
        help="with --kill-matrix: comma-separated kill coordinates "
        "(default: the full matrix sum:1,update:2,journal:sections:2,sum2:1,"
        "unmask:publish:1); "
        "CI smoke runs a one-per-phase-family subset",
    )
    ap.add_argument(
        "--mask",
        default=None,
        metavar="GROUP/DATA/BOUND/MODEL",
        help="with --kill-matrix: the mask configuration, e.g. integer/f32/b0/m6 "
        "(default: the shipped one)",
    )
    ap.add_argument(
        "--batch-size",
        type=int,
        default=1,
        metavar="K",
        help="with --kill-matrix: the fold batch; a journal entry is written "
        "after each (default 1: before every acknowledgement)",
    )
    ap.add_argument(
        "--updates",
        type=int,
        default=3,
        metavar="N",
        help="with --kill-matrix: uploads a round, whole fold batches (default 3)",
    )
    ap.add_argument(
        "--state-dir",
        default=None,
        metavar="DIR",
        help="with --kill-matrix: where the coordinators' durable trees are "
        "made, one a coordinate, and removed at the end (default: the "
        "system's temporary directory)",
    )
    ap.add_argument(
        "--faults",
        type=int,
        default=None,
        metavar="SEED",
        help="chaos soak: replay a seeded FaultPlan against the live "
        "coordinator (transient storage errors + latency across all "
        "components); rounds must still complete because the resilience "
        "layer retries them in place",
    )
    ap.add_argument(
        "--fault-spec",
        default=None,
        help="override the generated plan ('seed=' is prepended from --faults); "
        "see xaynet_tpu.resilience.faults for the grammar",
    )
    args = ap.parse_args()
    if args.wire_ingest and not args.device_kernel:
        ap.error("--wire-ingest requires --device-kernel")
    if args.kill_matrix:
        if (
            args.tenants is not None
            or args.tenant_churn
            or args.edges is not None
            or args.dropout is not None
            or args.stragglers is not None
            or args.faults is not None
        ):
            ap.error("--kill-matrix is a separate soak (it owns its own "
                     "process lifecycle and durable tree)")
        run_kill_matrix_soak(args)
        return
    if args.kill_points:
        ap.error("--kill-points requires --kill-matrix")
    if args.tenant_churn:
        if (
            args.tenants is not None
            or args.edges is not None
            or args.dropout is not None
            or args.stragglers is not None
            or args.faults is not None
        ):
            ap.error("--tenant-churn is a separate soak (it owns its own "
                     "tenant set and fault plan)")
        run_tenant_churn_soak(args)
        return
    if args.tenants is not None:
        if args.tenants < 2:
            ap.error("--tenants must be >= 2 (one tenant is the ordinary soak)")
        if args.edges or args.dropout is not None or args.stragglers is not None:
            ap.error("--tenants is a separate soak from --edges/--dropout")
        run_multi_tenant_soak(args)
        return
    chaos = args.dropout is not None or args.stragglers is not None
    dropout = args.dropout or 0.0
    stragglers = args.stragglers or 0
    if args.edges is not None:
        if args.edges < 1:
            ap.error("--edges must be >= 1")
        if chaos:
            ap.error("--edges and --dropout/--stragglers are separate soaks")
        if args.edge_updaters < 1:
            ap.error("--edge-updaters must be >= 1")
    two_tier_updaters = (args.edges or 0) * args.edge_updaters
    if chaos:
        if not (0.0 <= dropout < 1.0):
            ap.error("--dropout must be in [0, 1)")
        survivors = N_CHAOS_UPDATERS - int(round(N_CHAOS_UPDATERS * dropout))
        if survivors < 3:  # UPDATE_COUNT_MIN: below this no quorum can help
            ap.error(
                f"--dropout {dropout} leaves {survivors} of {N_CHAOS_UPDATERS} "
                "updaters; the PET update floor is 3"
            )
        if stragglers < 0 or stragglers > survivors:
            ap.error("--stragglers must be in [0, survivors]")
    if args.fault_spec is not None and args.faults is None:
        ap.error("--fault-spec requires --faults")
    if args.fault_spec is not None and "seed=" in args.fault_spec:
        # FaultPlan.parse lets a later seed= clause win, which would
        # silently override --faults and defeat a seed sweep
        ap.error("--fault-spec must not contain 'seed=' (use --faults)")

    fault_plan = None
    if args.faults is not None:
        spec = args.fault_spec or (
            # steady trickle of transient faults + latency over every
            # storage component; bounded so the tail of the soak runs clean
            "storage.coordinator.*:error,rate=0.02,max=50;"
            "storage.models.*:error,rate=0.02,max=20;"
            "storage.*:latency,rate=0.02,delay=0.02,max=100"
        )
        fault_plan = f"seed={args.faults};{spec}"
        # fail fast on a bad spec before booting a coordinator around it
        from xaynet_tpu.resilience.faults import FaultPlan

        FaultPlan.parse(fault_plan)

    with tempfile.TemporaryDirectory() as tmp:
        cfg_path = os.path.join(tmp, "config.toml")
        with open(cfg_path, "w") as f:
            f.write(
                CONFIG.format(
                    port=args.port,
                    model_len=args.model_len,
                    model_dir=os.path.join(tmp, "models"),
                    agg_device="true" if args.device_kernel else "false",
                    agg_wire_ingest="true" if args.wire_ingest else "false",
                    # keep the host-path default (64) so plain-soak numbers
                    # stay comparable across rounds; small batches only for
                    # the device path so every round actually flushes
                    agg_batch=2 if args.device_kernel else 64,
                    agg_kernel=args.device_kernel or "auto",
                    # churn soak: full updater fan-in as the window, quorum
                    # at the floor so dropped-out rounds close degraded;
                    # two-tier soak: the window is the full edge fan-in
                    update_min=(
                        two_tier_updaters
                        if args.edges
                        else (N_CHAOS_UPDATERS if chaos else 3)
                    ),
                    update_max=(
                        two_tier_updaters
                        if args.edges
                        else (N_CHAOS_UPDATERS if chaos else 3)
                    ),
                    update_quorum_line="quorum = 3" if chaos else "",
                    # stragglers delay 0.3s: inside the grace, so they count
                    stall_grace=1.0,
                    edge_enabled_line="[edge]\nenabled = true" if args.edges else "",
                )
            )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # flight-recorder dumps must SURVIVE the soak's tempdir: a failed
        # chaos round's forensics are the whole point of keeping them
        # (mkdtemp outside `tmp`; the path is printed in the result JSON
        # and on any failure)
        flight_dir = tempfile.mkdtemp(prefix="xaynet-soak-flight-")
        env["XAYNET_FLIGHT_DIR"] = flight_dir
        os.environ["XAYNET_FLIGHT_DIR"] = flight_dir  # SDK-side triggers too
        if fault_plan is not None:
            env["XAYNET_FAULT_PLAN"] = fault_plan
        if args.device_kernel:
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
        coord_log_path = os.path.join(tmp, "coordinator.log")
        coord_log = open(coord_log_path, "w")
        edge_procs, edge_ports, edge_logs = [], [], []
        proc = subprocess.Popen(
            [sys.executable, "-m", "xaynet_tpu.server.runner", "-c", cfg_path],
            env=env,
            stdout=coord_log,
            stderr=subprocess.STDOUT,
        )
        try:
            # wait until the coordinator actually listens (loaded CI hosts
            # can take longer than any fixed sleep)
            import socket

            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    with socket.create_connection(("127.0.0.1", args.port), timeout=1):
                        break
                except OSError:
                    if proc.poll() is not None:
                        raise RuntimeError("coordinator exited during startup")
                    time.sleep(0.25)
            else:
                raise RuntimeError("coordinator did not start listening in 60s")
            if args.edges:
                for i in range(args.edges):
                    edge_port = args.port + 1 + i
                    edge_cfg = os.path.join(tmp, f"edge{i}.toml")
                    with open(edge_cfg, "w") as f:
                        f.write(
                            EDGE_CONFIG.format(
                                port=edge_port,
                                upstream_port=args.port,
                                edge_id=f"edge-{i}",
                                max_members=args.edge_updaters,
                            )
                        )
                    edge_log = open(os.path.join(tmp, f"edge{i}.log"), "w")
                    edge_logs.append(edge_log)
                    edge_procs.append(
                        subprocess.Popen(
                            [sys.executable, "-m", "xaynet_tpu.edge.runner",
                             "-c", edge_cfg],
                            env=env,
                            stdout=edge_log,
                            stderr=subprocess.STDOUT,
                        )
                    )
                    edge_ports.append(edge_port)
                deadline = time.time() + 60
                pending_ports = list(edge_ports)
                while pending_ports and time.time() < deadline:
                    try:
                        with socket.create_connection(
                            ("127.0.0.1", pending_ports[0]), timeout=1
                        ):
                            pending_ports.pop(0)
                    except OSError:
                        time.sleep(0.25)
                if pending_ports:
                    raise RuntimeError("edge processes did not start listening in 60s")
            rss_start = _rss_kb(proc.pid)
            # warmup block first: the first rounds pay one-time costs (JIT
            # compiles, XLA buffer pools, import side-effects) that are not
            # per-round growth; the steady-state rate is what a leak looks
            # like
            warmup_rounds = min(20, max(1, args.rounds // 10))

            def run_block(n_rounds: int) -> dict:
                if args.edges:
                    return run_two_tier_soak_sync(
                        args.port, edge_ports, n_rounds, args.model_len,
                        two_tier_updaters,
                    )
                if chaos:
                    return run_chaos_soak_sync(
                        args.port, n_rounds, args.model_len, dropout, stragglers
                    )
                return run_soak_sync(args.port, n_rounds, args.model_len)

            def _flight_dumps() -> list:
                try:
                    return sorted(
                        os.path.join(flight_dir, f)
                        for f in os.listdir(flight_dir)
                        if f.startswith("flight_")
                    )
                except OSError:
                    return []

            try:
                run_block(warmup_rounds)
                rss_warm = _rss_kb(proc.pid)
                result = run_block(args.rounds)
            except Exception as err:
                # a failed/non-identical round stops being
                # reproduce-from-scratch: name the forensic bundles the
                # coordinator/edges dumped on the way down
                dumps = _flight_dumps()
                print(
                    json.dumps(
                        {
                            "soak_failed": str(err),
                            "flight_dir": flight_dir,
                            "flight_dumps": dumps,
                        }
                    ),
                    file=sys.stderr,
                )
                raise
            rss_end = _rss_kb(proc.pid)
            resolved = None
            if args.device_kernel:
                # the aggregator logs its per-round kernel resolution; the
                # LAST line is the steady-state answer (VERDICT r05 item 7:
                # the soak artifact must name the resolved kernel)
                coord_log.flush()
                with open(coord_log_path) as lf:
                    for line in lf:
                        if "aggregation kernel resolved:" in line:
                            resolved = line.rsplit("resolved:", 1)[1].strip()
            result.update(
                {
                    "rounds_per_s": round(result["rounds"] / result["wall_s"], 2),
                    "warmup_rounds": warmup_rounds,
                    "rss_start_kb": rss_start,
                    "rss_warm_kb": rss_warm,
                    "rss_end_kb": rss_end,
                    "rss_steady_kb_per_round": round(
                        (rss_end - rss_warm) / max(result["rounds"], 1), 1
                    ),
                    "kernel_requested": args.device_kernel,
                    "kernel_resolved": resolved,
                    "edges": args.edges,
                    "fault_plan": fault_plan,
                    "dropout": dropout if chaos else None,
                    "stragglers": stragglers if chaos else None,
                    "flight_dir": flight_dir,
                    "flight_dumps": _flight_dumps(),
                    "console": _scrape_console(args.port),
                }
            )
            print(json.dumps(result))
        finally:
            for ep in edge_procs:
                ep.terminate()
            proc.terminate()
            for ep in edge_procs:
                try:
                    ep.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    ep.kill()
                    ep.wait(timeout=5)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5)
            coord_log.close()
            for el in edge_logs:
                el.close()


if __name__ == "__main__":
    main()
