"""Coordinator process wiring and entry point.

Functional port of the reference's startup (reference:
rust/xaynet-server/src/bin/main.rs:29-138): settings -> logging -> metrics ->
store -> state-machine initializer -> REST server, with the state machine
and the API as the two long-lived tasks.

Run:  python -m xaynet_tpu.server.runner -c configs/config.toml
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
from typing import Optional

from ..storage.memory import (
    FileCoordinatorStorage,
    FilesystemModelStorage,
    InMemoryCoordinatorStorage,
    InMemoryModelStorage,
    NoOpTrustAnchor,
)
from ..storage.traits import Store
from ..telemetry import BridgedMetrics, RoundReporter
from ..telemetry.startup import get_timeline
from ..utils import tracing
from .aggregation import slots_take_planes
from .metrics import InfluxHttpMetrics, InfluxLineMetrics, JsonlMetrics, LogMetrics
from .rest import RestServer
from .services import Fetcher, PetMessageHandler
from .settings import Settings
from .state_machine import StateMachineInitializer

logger = logging.getLogger("xaynet.coordinator")


def init_store(settings: Settings, tenant: str = "default") -> Store:
    # tenant-scoped storage keys (docs/DESIGN.md §19): a non-default tenant
    # prefixes every durable key — redis keys get "t:<tenant>:", file/
    # filesystem backends get a "t-<tenant>" subtree — so N tenants share
    # one backend without key collisions. The default tenant keeps the
    # historical flat layout (single-tenant deployments are unchanged).
    scoped_dir = settings.storage.model_dir
    if tenant != "default":
        import os as _os

        scoped_dir = _os.path.join(settings.storage.model_dir, f"t-{tenant}")
    if settings.storage.coordinator == "redis":
        from ..storage.redis import RedisCoordinatorStorage

        coordinator = RedisCoordinatorStorage(
            host=settings.storage.redis_host,
            port=settings.storage.redis_port,
            db=settings.storage.redis_db,
            key_prefix="" if tenant == "default" else f"t:{tenant}:",
        )
    elif settings.storage.coordinator == "file":
        import os

        os.makedirs(scoped_dir, exist_ok=True)
        coordinator = FileCoordinatorStorage(
            os.path.join(scoped_dir, "coordinator_state.json")
        )
    else:
        coordinator = InMemoryCoordinatorStorage()
    if settings.storage.backend == "filesystem":
        models = FilesystemModelStorage(scoped_dir)
    elif settings.storage.backend == "s3":
        from ..storage.s3 import S3ModelStorage

        models = S3ModelStorage(
            endpoint=settings.storage.s3_endpoint,
            bucket=settings.storage.s3_bucket,
            access_key=settings.storage.s3_access_key,
            secret_key=settings.storage.s3_secret_key,
            region=settings.storage.s3_region,
        )
    else:
        # memory archives EVERY round's model in RAM (a slow leak in a
        # long-running coordinator) — fine for tests/benches, wrong for
        # production; configs/config.toml documents filesystem as default
        logging.getLogger("xaynet.runner").warning(
            "model storage backend 'memory' keeps all round models in RAM; "
            "use [storage] backend = \"filesystem\" in production"
        )
        models = InMemoryModelStorage()
    return Store(coordinator, models, NoOpTrustAnchor())


def init_metrics(settings: Settings):
    if not settings.metrics.enable:
        return None
    if settings.metrics.sink == "jsonl":
        return JsonlMetrics(settings.metrics.path)
    if settings.metrics.sink == "influx":
        return InfluxLineMetrics(settings.metrics.path)
    if settings.metrics.sink == "influx-http":
        return InfluxHttpMetrics(settings.metrics.url, settings.metrics.database)
    return LogMetrics()


def init_logging(settings: Settings) -> None:
    """Default logging with request-id correlation: every record carries
    ``%(request_id)s`` (set by ``tracing.RequestIdFilter`` from the
    contextvar the message pipeline assigns), so one grep on an id yields
    the full path of a message through pipeline and state machine."""
    logging.basicConfig(
        level=getattr(logging, settings.log.filter.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s [%(request_id)s] %(message)s",
    )
    # the filter must sit on the handlers: logger-level filters don't apply
    # to records propagated from child loggers
    for handler in logging.getLogger().handlers:
        if not any(isinstance(f, tracing.RequestIdFilter) for f in handler.filters):
            handler.addFilter(tracing.RequestIdFilter())


def _health_sections(handler: PetMessageHandler, device_report, resilience):
    """The runner's own sections of ``/healthz``: the size of the process's
    ``pet-msg`` pool, the round journal's writes, and the device's report
    where one aggregates."""
    from ..telemetry import journal

    def report() -> dict:
        out = {
            "message_workers": handler.workers.size,
            "journal": journal.report(
                resilience.checkpoint_enabled, resilience.checkpoint_every_batches
            ),
        }
        if device_report is not None:
            out.update(device_report())
        return out

    return report


def _mark_serving(startup) -> None:
    """The API accepts requests: the timeline's last mark, and from the same
    marks the restart-to-serving wall (docs/DESIGN.md §9): entry of
    ``serve()`` to the API accepting requests, store restore + journal
    resume included — THE recovery-time number the kill-matrix gate tracks."""
    from ..resilience.checkpoint import RECOVERY_SECONDS

    startup.mark("serving")
    RECOVERY_SECONDS.set(startup.seconds("imports", "serving"))


async def serve(settings: Settings, store: Optional[Store] = None) -> None:
    if settings.tenancy.enabled:
        # multi-tenant wiring: one process, one REST listener, N tenant
        # round pipelines over the shared mesh/pool/scheduler (§19)
        await serve_tenants(settings)
        return
    startup = get_timeline()
    startup.mark("imports")
    init_logging(settings)
    device_report = init_device_backend(settings)
    startup.mark("backend")
    store = store if store is not None else init_store(settings)
    if settings.storage.backend == "s3":
        # reference creates the bucket at startup (main.rs init_store path)
        from ..storage.s3 import S3ModelStorage

        if isinstance(store.models, S3ModelStorage):
            await store.models.create_bucket()
    # deterministic chaos: a configured fault plan installs process-wide
    # BEFORE the resilient wrapper, so storage/ingest/streaming sites all
    # see the same seeded schedule (tools/soak.py --faults drives this)
    if settings.resilience.fault_plan:
        from ..resilience import FaultPlan, install_plan

        install_plan(FaultPlan.parse(settings.resilience.fault_plan))
        logger.warning("fault plan installed: %s", settings.resilience.fault_plan)
    # every storage call flows through retry + circuit breaker from here on
    from ..resilience import wrap_store

    store = wrap_store(store, settings.resilience)
    startup.mark("store")
    # registry-first telemetry: the configured sink (if any) and the
    # per-round JSON reporter both consume the bridge's measurements
    reporter = (
        RoundReporter(settings.metrics.round_report_path)
        if settings.metrics.round_report_path
        else None
    )
    metrics = BridgedMetrics(sink=init_metrics(settings), reporter=reporter)
    # distributed round tracing + flight recorder (docs/DESIGN.md §16):
    # [metrics] trace/trace_dir/flight_dir override the env defaults
    from ..telemetry import recorder as flight_recorder, tracing as trace

    trace.get_tracer().configure(
        # empty settings defer to the env defaults the Tracer already read
        # (XAYNET_TRACE / XAYNET_TRACE_DIR); explicit config wins
        mode=settings.metrics.trace or None,
        trace_dir=settings.metrics.trace_dir or None,
    )
    flight_recorder.get_recorder().configure(settings.metrics.flight_dir or None)
    # per-tenant SLO targets + burn-rate alerting over the always-on
    # round-wall timeline (docs/DESIGN.md §20)
    from ..telemetry import slo as slo_engine

    slo_engine.configure(settings.slo)
    # warm kernel-calibration verdicts (docs/DESIGN.md §22): with
    # XAYNET_CALIB_CACHE set, the fold/mask probe races a previous process
    # ran load here instead of inside the first round's wall
    from ..utils import calibcache

    calibcache.configure_from_env()
    initializer = StateMachineInitializer(settings, store, metrics)
    machine, request_tx, events = await initializer.init()
    startup.mark("machine")

    handler = PetMessageHandler(
        events,
        request_tx,
        wire_ingest=settings.aggregation.wire_ingest,
        update_planes=slots_take_planes(settings),
    )
    fetcher = Fetcher(events)
    pipeline = None
    if settings.ingest.enabled:
        from ..ingest import IngestPipeline

        pipeline = IngestPipeline(handler, request_tx, events, settings.ingest)
        await pipeline.start()
    edge_api = None
    if settings.edge.enabled:
        from ..edge.api import EdgeCoordinatorApi

        edge_api = EdgeCoordinatorApi(events, request_tx, token=settings.edge.token)
        logger.info("edge tier enabled: serving /edge/round + /edge/envelope")
    rest = RestServer(
        fetcher,
        handler,
        registry=metrics.registry,
        pipeline=pipeline,
        edge_api=edge_api,
        health_extra=_health_sections(handler, device_report, settings.resilience),
    )
    host, _, port = settings.api.bind_address.partition(":")
    tls = None
    if settings.api.tls_certificate:
        import ssl

        tls = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        tls.load_cert_chain(settings.api.tls_certificate, settings.api.tls_key)
        if settings.api.tls_client_auth:
            tls.verify_mode = ssl.CERT_REQUIRED
            tls.load_verify_locations(settings.api.tls_client_auth)
    await rest.start(host or "127.0.0.1", int(port or 8081), tls)
    _mark_serving(startup)

    stop = asyncio.get_running_loop().create_future()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            asyncio.get_running_loop().add_signal_handler(sig, lambda: stop.cancel())
        except NotImplementedError:  # pragma: no cover (non-unix)
            pass

    machine_task = asyncio.create_task(machine.run())
    try:
        done, _ = await asyncio.wait(
            [machine_task, stop], return_when=asyncio.FIRST_COMPLETED
        )
    except asyncio.CancelledError:
        pass
    finally:
        # graceful-signal flush (docs/DESIGN.md §9): capture the running
        # phase's journal hook BEFORE cancelling — a SIGTERM between the
        # update phase's save cadence points must not drop accepted updates
        phase = machine.phase
        flush = getattr(phase.shared, "flush_hook", None) if phase is not None else None
        machine_task.cancel()
        await asyncio.gather(machine_task, return_exceptions=True)
        if flush is not None:
            try:
                await flush()
                logger.info("graceful shutdown: final journal entry flushed")
            except Exception as err:
                logger.warning("graceful shutdown: journal flush failed: %s", err)
        # a cancelled machine never reaches the Shutdown phase, so close the
        # request channel here: queued/in-flight requests are rejected and
        # the pipeline's final coalescer flush fails fast instead of
        # awaiting a state machine that will never answer
        request_tx.close()
        await rest.stop()
        if pipeline is not None:
            await pipeline.stop()
        # flush the in-flight round report and drain the dispatcher thread's
        # queued tail — without this the InfluxHttp dispatcher dies with
        # whatever was still batching
        metrics.close()
        # forensic tail: the flight ring (recent spans + counter deltas)
        # lands on disk with every orderly exit, so a post-mortem has the
        # same bundle a crash dump would carry
        flight_recorder.flight_dump(
            "shutdown", "coordinator stopping (signal or machine exit)"
        )
        # ... and the in-flight round's trace window (Chrome export)
        trace.get_tracer().end_round()
        logger.info("coordinator stopped")


def _tenant_settings(base: Settings, tenant: str) -> Settings:
    """One tenant's effective settings: ``config_dir/<tenant>.toml`` when
    present (full settings file, normal loader + env overrides), else a
    copy of the base. The per-tenant copy never re-enters multi-tenant
    wiring (its [tenancy] section is cleared)."""
    import copy

    from .settings import TenancySettings

    cfg = None
    if base.tenancy.config_dir:
        path = os.path.join(base.tenancy.config_dir, f"{tenant}.toml")
        if os.path.exists(path):
            cfg = Settings.load(path)
            logger.info("tenant %s: settings loaded from %s", tenant, path)
    if cfg is None:
        cfg = copy.deepcopy(base)
    cfg.tenancy = TenancySettings()
    return cfg


async def _build_tenant_context(settings: Settings, tenant: str, budget, registry):
    """Build ONE tenant's full round pipeline and register it: scoped
    store, resilient wrapper, metrics bridge, phase machine, handler,
    fetcher, ingest pipeline and edge api. Shared by the serve_tenants
    boot loop and the lifecycle manager's runtime onboard — the runtime
    path builds tenants with exactly the wiring boot-time ones get.
    Returns ``(TenantContext, TenantRoutes)`` (the machine task is NOT
    started here; the caller owns task lifetime)."""
    from ..ingest import IngestPipeline
    from ..resilience import wrap_store
    from ..tenancy import TenantContext
    from .rest import TenantRoutes

    tset = _tenant_settings(settings, tenant)
    startup = get_timeline()  # a boot's first tenant marks; later ones find the steps marked
    device_report = init_device_backend(tset)
    startup.mark("backend")
    raw_store = init_store(tset, tenant)
    if tset.storage.backend == "s3":
        # same startup contract as the single-tenant serve() path:
        # the bucket must exist before the first model save
        from ..storage.s3 import S3ModelStorage

        if isinstance(raw_store.models, S3ModelStorage):
            await raw_store.models.create_bucket()
    store = wrap_store(raw_store, tset.resilience, tenant=tenant)
    startup.mark("store")
    reporter = (
        RoundReporter(tset.metrics.round_report_path, tenant=tenant)
        if tset.metrics.round_report_path
        else None
    )
    metrics = BridgedMetrics(sink=init_metrics(tset), reporter=reporter)
    initializer = StateMachineInitializer(tset, store, metrics, tenant=tenant)
    machine, request_tx, events = await initializer.init()
    handler = PetMessageHandler(
        events,
        request_tx,
        wire_ingest=tset.aggregation.wire_ingest,
        update_planes=slots_take_planes(tset),
    )
    fetcher = Fetcher(events)
    pipeline = None
    if tset.ingest.enabled:
        pipeline = IngestPipeline(
            handler, request_tx, events, tset.ingest,
            tenant=tenant, budget=budget,
        )
        await pipeline.start()
    edge_api = None
    if tset.edge.enabled:
        from ..edge.api import EdgeCoordinatorApi

        edge_api = EdgeCoordinatorApi(events, request_tx, token=tset.edge.token)
    ctx = registry.add(
        TenantContext(
            tenant=tenant,
            settings=tset,
            store=store,
            machine=machine,
            request_tx=request_tx,
            events=events,
            handler=handler,
            fetcher=fetcher,
            pipeline=pipeline,
            edge_api=edge_api,
            metrics=metrics,
        )
    )
    troutes = TenantRoutes(
        fetcher=fetcher,
        handler=handler,
        pipeline=pipeline,
        edge_api=edge_api,
        health_extra=_health_sections(handler, device_report, settings.resilience),
    )
    logger.info(
        "tenant %s: model_len=%d group=%s (round pipeline up)",
        tenant,
        tset.model.length,
        tset.mask.group_type.name,
    )
    return ctx, troutes


async def serve_tenants(settings: Settings) -> None:
    """Multi-tenant coordinator (docs/DESIGN.md §19, §23): one process
    serves every ``[tenancy] tenants`` id — each a full, independent round
    pipeline (scoped store, request channel, ingest, phase machine) —
    over ONE mesh, ONE paged accumulator pool, ONE fold-batch scheduler
    and ONE REST listener routing ``/t/<tenant>/...`` (the first tenant
    also serves the bare legacy routes). With ``[tenancy] admin_token``
    set, the tenant set is ELASTIC: ``/admin/tenants`` onboards, drains
    and reconfigures tenants at runtime through the lifecycle manager."""
    from ..telemetry import recorder as flight_recorder, tracing as trace
    from ..tenancy import (
        TenantAdmissionBudget,
        TenantLifecycle,
        TenantRegistry,
        configure_pool,
        configure_scheduler,
        install_manager,
    )
    from .rest import TenantRoutes

    startup = get_timeline()
    startup.mark("imports")
    init_logging(settings)
    ten = settings.tenancy
    configure_pool(ten.page_kib, ten.slab_pages, ten.host_pages)
    configure_scheduler(ten.max_inflight_folds)
    budget = TenantAdmissionBudget(ten.ingest_capacity, ten.max_share)
    if settings.resilience.fault_plan:
        from ..resilience import FaultPlan, install_plan

        install_plan(FaultPlan.parse(settings.resilience.fault_plan))
        logger.warning("fault plan installed: %s", settings.resilience.fault_plan)
    trace.get_tracer().configure(
        mode=settings.metrics.trace or None,
        trace_dir=settings.metrics.trace_dir or None,
    )
    flight_recorder.get_recorder().configure(settings.metrics.flight_dir or None)
    # the SLO engine is process-wide (per-tenant state inside): configured
    # once from the base settings' [slo] section, tenant targets included
    from ..telemetry import slo as slo_engine

    slo_engine.configure(settings.slo)
    from ..utils import calibcache

    calibcache.configure_from_env()

    registry = TenantRegistry()
    routes: dict[str, TenantRoutes] = {}
    for tenant in ten.tenants:
        _, troutes = await _build_tenant_context(settings, tenant, budget, registry)
        routes[tenant] = troutes
    startup.mark("machine")  # every tenant's; backend and store are the first one's

    # elastic lifecycle (docs/DESIGN.md §23): the manager owns runtime
    # onboard/drain over the SAME builder the boot loop used, fault
    # quarantine fed by the phase close paths, and the SLO->scheduler
    # demotion feedback loop
    lifecycle = TenantLifecycle(
        ten,
        registry,
        routes,
        budget=budget,
        builder=lambda t: _build_tenant_context(settings, t, budget, registry),
    )
    install_manager(lifecycle)
    lifecycle.install_slo_hook(slo_engine.get_engine())
    for tenant in registry.ids():
        lifecycle.mark_serving(tenant)

    default = registry.default
    rest = RestServer(
        default.fetcher,
        default.handler,
        registry=default.metrics.registry,
        pipeline=default.pipeline,
        edge_api=default.edge_api,
        health_extra=routes[default.tenant].health_extra,
        tenants=routes,
        lifecycle=lifecycle,
        admin_token=ten.admin_token,
        default_tenant=default.tenant,
    )
    host, _, port = settings.api.bind_address.partition(":")
    tls = None
    if settings.api.tls_certificate:
        import ssl

        tls = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        tls.load_cert_chain(settings.api.tls_certificate, settings.api.tls_key)
        if settings.api.tls_client_auth:
            tls.verify_mode = ssl.CERT_REQUIRED
            tls.load_verify_locations(settings.api.tls_client_auth)
    await rest.start(host or "127.0.0.1", int(port or 8081), tls)
    # EVERY tenant's store restore + journal resume ran before the listener
    # came up (each tenant resumes independently from its scoped journal)
    _mark_serving(startup)
    logger.info(
        "multi-tenant coordinator up: %d tenants (%s), default=%s",
        len(registry),
        ", ".join(registry.ids()),
        default.tenant,
    )

    stop = asyncio.get_running_loop().create_future()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            asyncio.get_running_loop().add_signal_handler(sig, lambda: stop.cancel())
        except NotImplementedError:  # pragma: no cover (non-unix)
            pass

    for ctx in registry.contexts():
        ctx.task = asyncio.create_task(
            ctx.machine.run(), name=f"machine-{ctx.tenant}"
        )
    try:
        # the task set is DYNAMIC under the elastic lifecycle: drained
        # tenants' tasks get cancelled (that must not stop the process),
        # onboarded tenants add new ones — so re-derive the watch set from
        # the registry each pass and only exit when a task belonging to a
        # still-registered tenant finishes (a machine reaching Shutdown)
        # or the stop future fires
        while True:
            tasks = [c.task for c in registry.contexts() if c.task is not None]
            done, _ = await asyncio.wait(
                [*tasks, stop], return_when=asyncio.FIRST_COMPLETED
            )
            if stop in done:
                break
            live = {c.task for c in registry.contexts()}
            if any(t in live for t in done):
                break
    except asyncio.CancelledError:
        pass
    finally:
        from ..tenancy import install_manager as _uninstall

        _uninstall(None)
        # graceful-signal flush, per tenant: capture each running phase's
        # journal hook BEFORE cancelling its machine task
        flushes = []
        for ctx in registry.contexts():
            phase = ctx.machine.phase
            hook = getattr(phase.shared, "flush_hook", None) if phase is not None else None
            if hook is not None:
                flushes.append((ctx.tenant, hook))
        tasks = [c.task for c in registry.contexts() if c.task is not None]
        for ctx in registry.contexts():
            if ctx.task is not None:
                ctx.task.cancel()
            # same rationale as the single-tenant path: reject queued +
            # in-flight requests so draining components fail fast —
            # strictly per channel, one tenant's shutdown never strands
            # another tenant's requests
            ctx.request_tx.close()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        for tenant, hook in flushes:
            try:
                await hook()
                logger.info("tenant %s: final journal entry flushed", tenant)
            except Exception as err:
                logger.warning("tenant %s: journal flush failed: %s", tenant, err)
        await rest.stop()
        for ctx in registry.contexts():
            if ctx.pipeline is not None:
                await ctx.pipeline.stop()
            ctx.metrics.close()
        flight_recorder.flight_dump(
            "shutdown", "multi-tenant coordinator stopping"
        )
        trace.get_tracer().end_round()
        logger.info("multi-tenant coordinator stopped")


class DeviceBackendError(RuntimeError):
    """``[aggregation] device = true`` cannot be honoured on this host."""


def init_device_backend(settings: Settings):
    """Start-up half of device aggregation: place the compile cache, make
    JAX pick its backend NOW, refuse a silent CPU, and say what was found.

    ``JAX_PLATFORMS`` is simply honoured by JAX. What this adds is the
    refusal: with ``device = true`` and the resolved backend ``cpu``, the
    operator must have NAMED cpu in ``JAX_PLATFORMS`` (tests, the CPU smoke)
    — otherwise a host whose accelerator is missing or held by another
    process would aggregate on XLA:CPU and look healthy.

    Returns the zero-arg ``/healthz`` hook reporting the backend, or None
    with host aggregation (which never imports jax).
    """
    if not settings.aggregation.device:
        return None
    import jax

    from ..utils import jaxcache

    cache_dir = jaxcache.enable_compile_cache()
    backend = jax.default_backend()
    named = [p.strip() for p in os.environ.get("JAX_PLATFORMS", "").split(",")]
    if backend == "cpu" and "cpu" not in named:
        raise DeviceBackendError(
            "[aggregation] device = true but JAX resolved the cpu backend "
            "(no accelerator found, or it is held by another process); set "
            "JAX_PLATFORMS=cpu to aggregate on XLA:CPU on purpose"
        )
    # the program's spans on the profiler's clock: with a profiler session
    # open they land in the device trace beside the device's operations;
    # with none an annotation costs an atomic load (docs/DESIGN.md §16)
    from ..telemetry import tracing as trace

    trace.get_tracer().set_mirror(jax.profiler.TraceAnnotation)
    devices = jax.devices()
    logger.info(
        "device aggregation on backend=%s device_kind=%s devices=%d; "
        "compile cache %s (%d entries)",
        backend,
        devices[0].device_kind,
        len(devices),
        cache_dir,
        jaxcache.compile_report()["cache_entries_start"],
    )
    return device_health


def device_health() -> dict:
    """The ``device`` section of ``/healthz``: what the coordinator runs
    on, which fold kernel it resolved (with the race record), what
    compiling cost so far, and each device's peak memory."""
    import jax

    from ..parallel.aggregator import fold_kernel_report
    from ..utils import jaxcache

    devices = jax.devices()
    peaks = []
    for dev in devices:
        stats = dev.memory_stats()  # None where the backend keeps none (cpu)
        peaks.append(stats.get("peak_bytes_in_use") if stats else None)
    return {
        "device": {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "peak_bytes_in_use": peaks,
            "fold": fold_kernel_report(),
            "compile": jaxcache.compile_report(),
        }
    }


def main() -> None:
    parser = argparse.ArgumentParser(description="xaynet-tpu coordinator")
    parser.add_argument("-c", "--config", help="TOML configuration file", default=None)
    args = parser.parse_args()
    settings = Settings.load(args.config)
    try:
        asyncio.run(serve(settings))
    except DeviceBackendError as err:
        raise SystemExit(f"error: {err}") from err


if __name__ == "__main__":
    main()
