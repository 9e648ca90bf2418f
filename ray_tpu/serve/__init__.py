"""ray_tpu.serve — model serving (Ray Serve analog, `python/ray/serve/`).

`@serve.deployment` → `.bind()` → `serve.run()`; replicas are async
actors behind a power-of-two-choices router; an aiohttp proxy provides
HTTP ingress; the controller reconciles replica counts and autoscales on
in-flight requests (`serve.run` call stack: SURVEY §3.5).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import ray_tpu
from ray_tpu.serve._private.controller import CONTROLLER_NAME, ServeController
from ray_tpu.serve.asgi import ingress  # noqa: F401
from ray_tpu.serve.batching import batch  # noqa: F401
from ray_tpu.serve.handle import DeploymentHandle, DeploymentResponse  # noqa: F401
from ray_tpu.serve.multiplex import (  # noqa: F401
    get_multiplexed_model_id,
    multiplexed,
)


def start_rpc_ingress(port: int = 0) -> int:
    """Start the binary RPC ingress (the reference's gRPC-ingress role over
    the framework's native framing); returns the bound port."""
    from ray_tpu.serve._private.rpc_ingress import start_rpc_ingress as _s

    return _s(port)


@dataclasses.dataclass
class AutoscalingConfig:
    """Analog of `ray.serve.config.AutoscalingConfig`."""

    min_replicas: int = 1
    max_replicas: int = 1
    target_ongoing_requests: float = 2.0
    # retire nodes fully vacated by an autoscaler scale-down via the
    # controller's node_drain RPC (immediate channel/pin/lease handoff,
    # no crash debounce). Opt-in: a drain takes the whole node, so this
    # is only safe when the autoscaled replica pool owns its nodes.
    drain_nodes: bool = False

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class Application:
    """A bound deployment graph node (reference: `Deployment.bind`
    `python/ray/serve/deployment.py:245`)."""

    def __init__(self, deployment: "Deployment", args: Tuple, kwargs: Dict):
        self.deployment = deployment
        self.args = args
        self.kwargs = kwargs


class Deployment:
    def __init__(self, func_or_class: Any, name: str,
                 num_replicas: int = 1,
                 max_ongoing_requests: int = 8,
                 ray_actor_options: Optional[Dict] = None,
                 autoscaling_config: Optional[Union[Dict,
                                                    AutoscalingConfig]] = None,
                 user_config: Any = None):
        self.func_or_class = func_or_class
        self.name = name
        self.num_replicas = num_replicas
        self.max_ongoing_requests = max_ongoing_requests
        self.ray_actor_options = ray_actor_options or {}
        if isinstance(autoscaling_config, AutoscalingConfig):
            autoscaling_config = autoscaling_config.to_dict()
        self.autoscaling_config = autoscaling_config
        self.user_config = user_config

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)

    def options(self, **overrides) -> "Deployment":
        fields = dict(
            func_or_class=self.func_or_class, name=self.name,
            num_replicas=self.num_replicas,
            max_ongoing_requests=self.max_ongoing_requests,
            ray_actor_options=dict(self.ray_actor_options),
            autoscaling_config=self.autoscaling_config,
            user_config=self.user_config)
        fields.update(overrides)
        return Deployment(**fields)

    def _spec(self, init_args: Tuple, init_kwargs: Dict) -> Dict[str, Any]:
        cls = self.func_or_class
        num = self.num_replicas
        if self.autoscaling_config:
            num = max(num, self.autoscaling_config.get("min_replicas", 1))
        return {
            "name": self.name,
            "num_replicas": num,
            "max_ongoing_requests": self.max_ongoing_requests,
            "ray_actor_options": self.ray_actor_options,
            "autoscaling_config": self.autoscaling_config,
            "user_config": self.user_config,
            "callable_factory": lambda: cls,
            "init_args": init_args,
            "init_kwargs": init_kwargs,
        }


def deployment(_func_or_class: Optional[Any] = None, *,
               name: Optional[str] = None,
               num_replicas: int = 1,
               max_ongoing_requests: int = 8,
               ray_actor_options: Optional[Dict] = None,
               autoscaling_config: Optional[Union[Dict,
                                                  AutoscalingConfig]] = None,
               user_config: Any = None):
    """`@serve.deployment` (reference `python/ray/serve/api.py`)."""

    def wrap(fc):
        return Deployment(fc, name or fc.__name__,
                          num_replicas=num_replicas,
                          max_ongoing_requests=max_ongoing_requests,
                          ray_actor_options=ray_actor_options,
                          autoscaling_config=autoscaling_config,
                          user_config=user_config)

    return wrap(_func_or_class) if _func_or_class is not None else wrap


# ----------------------------------------------------------------- control


def _get_or_create_controller():
    try:
        c = ray_tpu.get_actor(CONTROLLER_NAME)
        # the name registry may still hold a controller a previous
        # serve.shutdown killed — liveness-check before trusting it
        ray_tpu.get(c.get_routes.remote(), timeout=10)
        return c
    except Exception:
        return ray_tpu.remote(ServeController).options(
            name=CONTROLLER_NAME, lifetime="detached", num_cpus=0.1,
            max_concurrency=256).remote()


def _collect_specs(app: Application, specs: Dict[str, Dict],
                   ) -> DeploymentHandle:
    """DFS the bind graph; nested Applications become DeploymentHandles."""
    dep = app.deployment

    def resolve(v):
        if isinstance(v, Application):
            return _collect_specs(v, specs)
        return v

    init_args = tuple(resolve(a) for a in app.args)
    init_kwargs = {k: resolve(v) for k, v in app.kwargs.items()}
    if dep.name not in specs:
        specs[dep.name] = dep._spec(init_args, init_kwargs)
    return DeploymentHandle(_current_app_name, dep.name)


_current_app_name = "default"


def run(app: Application, *, name: str = "default",
        route_prefix: Optional[str] = "/", _blocking: bool = True,
        timeout_s: float = 60.0) -> DeploymentHandle:
    global _current_app_name
    _current_app_name = name
    controller = _get_or_create_controller()
    specs: Dict[str, Dict] = {}
    ingress_handle = _collect_specs(app, specs)
    ray_tpu.get(controller.deploy_application.remote(
        name, list(specs.values()), route_prefix, app.deployment.name))
    if _blocking:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            st = ray_tpu.get(controller.status.remote()).get(name, {})
            if st and all(d["status"] == "RUNNING" for d in st.values()):
                break
            failed = {n: d["error"] for n, d in st.items() if d.get("error")}
            if failed:
                raise RuntimeError(
                    f"application {name!r} failed to deploy: " + "; ".join(
                        f"a replica of {n!r} died in its constructor: {e}"
                        for n, e in failed.items()))
            time.sleep(0.1)
        else:
            raise TimeoutError(f"application {name!r} not RUNNING: {st}")
    ingress_handle._controller = controller
    return ingress_handle


def start(*, http_port: int = 8000) -> int:
    """Start the HTTP proxy (reference starts proxies on serve.start /
    first run; explicit here). Returns the bound port."""
    from ray_tpu.serve._private.proxy import ProxyActor

    controller = _get_or_create_controller()
    try:
        proxy = ray_tpu.get_actor("SERVE_PROXY")
    except Exception:
        proxy = ray_tpu.remote(ProxyActor).options(
            name="SERVE_PROXY", lifetime="detached", num_cpus=0.1,
            max_concurrency=256).remote(controller, http_port)
    return ray_tpu.get(proxy.ready.remote())


def get_app_handle(name: str = "default") -> DeploymentHandle:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    routes = ray_tpu.get(controller.get_routes.remote())
    for target in routes.values():
        app_name, dep = target.split("/", 1)
        if app_name == name:
            h = DeploymentHandle(app_name, dep, controller)
            return h
    raise ValueError(f"no application named {name!r}")


def get_deployment_handle(deployment_name: str,
                          app_name: str = "default") -> DeploymentHandle:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return DeploymentHandle(app_name, deployment_name, controller)


def status() -> Dict[str, Any]:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.status.remote())


def delete(name: str) -> None:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    ray_tpu.get(controller.delete_application.remote(name))


def shutdown() -> None:
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        return
    try:
        ray_tpu.get(controller.graceful_shutdown.remote())
    except Exception:
        pass
    for actor_name in ("SERVE_PROXY", "SERVE_RPC_INGRESS", CONTROLLER_NAME):
        try:
            ray_tpu.kill(ray_tpu.get_actor(actor_name))
        except Exception:
            pass

from ray_tpu._private.usage import record_library_usage as _rlu

_rlu("serve")
del _rlu
