"""SimulatorSession — one gRPC server hosting the selected control-plane
services (reference ``ols_core/simu_session.py:25-70``: boots
TaskMgr/ResourceMgr/RayClusterMgr/PerformanceMgr into one process by ``svc``
selector; here DeviceFlow and PhoneManager join the same process too, since
no external Pulsar/phone-farm processes are required in single-host mode).
"""

from __future__ import annotations

from concurrent import futures
from typing import Iterable, Optional, Tuple

import grpc

from olearning_sim_tpu.services.grpc_services import (
    DeviceFlowServicer,
    PerformanceMgrServicer,
    PhoneManagerServicer,
    ResourceMgrServicer,
    SliceMgrServicer,
    add_service_to_server,
)

ALL_SERVICES = ("taskmgr", "resourcemgr", "deviceflow", "phonemgr",
                "slicemgr", "performancemgr")


class SimulatorSession:
    """Compose managers into one served process.

    Any manager may be None (service omitted) — matching the reference's
    ``svc`` list selector. Construction wires defaults so
    ``SimulatorSession().start()`` gives a fully working single-host platform:
    ResourceManager over the local device topology, DeviceFlowService,
    PerformanceManager, ClusterManager, and a TaskManager wired to all of
    them (plus an optional SimulatedPhoneFarm).
    """

    def __init__(
        self,
        services: Iterable[str] = ALL_SERVICES,
        address: str = "127.0.0.1:0",
        task_manager=None,
        resource_manager=None,
        deviceflow=None,
        phone_farm=None,
        cluster_manager=None,
        performance_manager=None,
        max_workers: int = 16,
        metrics_port: Optional[int] = None,
        supervisor=None,
        supervise: bool = True,
    ):
        """``metrics_port`` — when set, start() also serves the telemetry
        registry on ``127.0.0.1:<metrics_port>`` (``/metrics`` Prometheus
        text, ``/metrics.json`` snapshot; 0 binds an ephemeral port,
        readable from ``session.metrics_server.port``).

        ``supervisor`` / ``supervise`` — crash-safe task supervision
        (docs/resilience.md): when the session hosts a task manager and
        ``supervise`` is on, a :class:`~olearning_sim_tpu.supervisor.
        TaskSupervisor` (the given one, or a default over the manager)
        starts/stops with the session, and a session-built manager recovers
        resume-first (orphaned RUNNING rows are left for the supervisor to
        reclaim instead of being failed on boot)."""
        self.services = tuple(services)
        self.address = address
        self._server: Optional[grpc.Server] = None
        self.port: Optional[int] = None
        self.metrics_port = metrics_port
        self.metrics_server = None

        if "resourcemgr" in self.services and resource_manager is None:
            from olearning_sim_tpu.resourcemgr.resource_manager import ResourceManager

            phone_provider = (
                phone_farm.get_device_available_resource
                if phone_farm is not None else None
            )
            resource_manager = ResourceManager(phone_provider=phone_provider)
        if "deviceflow" in self.services and deviceflow is None:
            from olearning_sim_tpu.deviceflow.service import DeviceFlowService

            deviceflow = DeviceFlowService()
        if "slicemgr" in self.services and cluster_manager is None:
            from olearning_sim_tpu.clustermgr import ClusterManager

            cluster_manager = ClusterManager()
        if "performancemgr" in self.services and performance_manager is None:
            from olearning_sim_tpu.performancemgr import PerformanceManager

            performance_manager = PerformanceManager()
        if "taskmgr" in self.services and task_manager is None:
            from olearning_sim_tpu.taskmgr.task_manager import TaskManager

            task_manager = TaskManager(
                resource_manager=resource_manager,
                deviceflow=deviceflow,
                phone_client=phone_farm,
                perf=performance_manager,
                supervise_orphans=supervise,
            )
        if (supervise and "taskmgr" in self.services
                and task_manager is not None):
            # A user-supplied manager must share the session's resume-first
            # posture, or its release loop would MISSING-fail orphans ahead
            # of the supervisor's reclaim. (Boot-time `_recover` already ran
            # at THAT manager's construction — managers built for a
            # supervised session should pass supervise_orphans=True
            # themselves to also recover resume-first.)
            task_manager._supervise_orphans = True
            if supervisor is None:
                from olearning_sim_tpu.supervisor import TaskSupervisor

                supervisor = TaskSupervisor(task_manager)
        self.supervisor = supervisor

        self.task_manager = task_manager
        self.resource_manager = resource_manager
        self.deviceflow = deviceflow
        self.phone_farm = phone_farm
        self.cluster_manager = cluster_manager
        self.performance_manager = performance_manager
        self._max_workers = max_workers

    # ------------------------------------------------------------------ boot
    def start(self) -> Tuple[grpc.Server, int]:
        """Bind the gRPC server and bring the services' threads up, under
        the ``session.start`` span (docs/observability.md): it belongs to
        no task, and its ``process_age_s`` is how long the process had
        lived when the span opened, so that age plus duration is the
        process's boot time, imports and backend start included."""
        from olearning_sim_tpu.telemetry import default_tracer, process_age_s

        attrs = {"services": list(self.services)}
        age_s = process_age_s()
        if age_s is not None:
            attrs["process_age_s"] = age_s
        with default_tracer().span("session.start", **attrs):
            return self._start()

    def _start(self) -> Tuple[grpc.Server, int]:
        server = grpc.server(futures.ThreadPoolExecutor(self._max_workers))
        if "taskmgr" in self.services and self.task_manager is not None:
            from olearning_sim_tpu.taskmgr.grpc_service import (
                TaskMgrServicer,
                add_taskmgr_to_server,
            )

            add_taskmgr_to_server(TaskMgrServicer(self.task_manager), server)
            self.task_manager.start()
            if self.supervisor is not None:
                self.supervisor.start()
        if "resourcemgr" in self.services and self.resource_manager is not None:
            add_service_to_server(ResourceMgrServicer(self.resource_manager), server)
        if "deviceflow" in self.services and self.deviceflow is not None:
            add_service_to_server(DeviceFlowServicer(self.deviceflow), server)
            self.deviceflow.start()
        if "phonemgr" in self.services and self.phone_farm is not None:
            add_service_to_server(PhoneManagerServicer(self.phone_farm), server)
        if "slicemgr" in self.services and self.cluster_manager is not None:
            add_service_to_server(SliceMgrServicer(self.cluster_manager), server)
        if "performancemgr" in self.services and self.performance_manager is not None:
            add_service_to_server(
                PerformanceMgrServicer(self.performance_manager), server
            )
        self.port = server.add_insecure_port(self.address)
        server.start()
        self._server = server
        if self.metrics_port is not None and self.metrics_server is None:
            from olearning_sim_tpu.telemetry import MetricsHTTPServer

            registry = getattr(self.performance_manager, "registry", None)
            self.metrics_server = MetricsHTTPServer(
                registry=registry, port=self.metrics_port
            ).start()
        return server, self.port

    def stop(self, grace: float = 1.0) -> None:
        if self._server is not None:
            self._server.stop(grace)
            self._server = None
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None
        if self.supervisor is not None:
            self.supervisor.stop()
        if self.task_manager is not None and hasattr(self.task_manager, "stop"):
            self.task_manager.stop()
        if self.deviceflow is not None and hasattr(self.deviceflow, "stop"):
            self.deviceflow.stop()

    def __enter__(self) -> "SimulatorSession":
        self.start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()
