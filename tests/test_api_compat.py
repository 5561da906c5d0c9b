"""Reference top-level API compatibility surface (ray: ray/__init__.py
__all__): mode constants, Language, LoggingConfig, get_gpu_ids/
get_tpu_ids, show_in_dashboard, ClientBuilder, submodule attributes."""
import json
import logging

import pytest

import ray_tpu


def test_mode_constants_and_language():
    assert (ray_tpu.SCRIPT_MODE, ray_tpu.WORKER_MODE,
            ray_tpu.LOCAL_MODE) == (0, 1, 2)
    assert ray_tpu.Language.PYTHON == "PYTHON"
    assert ray_tpu.Language.CPP == "CPP"
    # JAVA is the documented intentional gap — not present.
    assert not hasattr(ray_tpu.Language, "JAVA")


def test_submodules_reachable_as_attributes():
    assert hasattr(ray_tpu.autoscaler, "__path__")
    assert hasattr(ray_tpu.client, "probe")
    assert hasattr(ray_tpu.cluster_utils, "Cluster")


def test_gpu_and_tpu_ids_on_driver():
    assert ray_tpu.get_gpu_ids() == []
    # The driver is never the device worker.
    assert ray_tpu.get_tpu_ids() == []


def test_logging_config_validation_and_json_encoding():
    with pytest.raises(ValueError, match="encoding"):
        ray_tpu.LoggingConfig(encoding="YAML")
    with pytest.raises(ValueError, match="log level"):
        ray_tpu.LoggingConfig(log_level="CHATTY")
    from ray_tpu.logging_config import JsonFormatter

    rec = logging.LogRecord("t", logging.WARNING, __file__, 1,
                            "hello %s", ("world",), None)
    out = json.loads(JsonFormatter().format(rec))
    assert out["message"] == "hello world"
    assert out["levelname"] == "WARNING"
    assert out["name"] == "t"


def test_show_in_dashboard_from_task(ray_shared):
    @ray_tpu.remote
    def announce():
        ray_tpu.show_in_dashboard("phase 1 done", key="phase")
        ray_tpu.show_in_dashboard("<b>hi</b>", key="rich", dtype="html")
        return ray_tpu.get_runtime_context().get_worker_id()

    wid = ray_tpu.get(announce.remote(), timeout=120)
    from ray_tpu._private.worker import global_worker

    core = global_worker()
    reply, blobs = core.call(core.controller_addr, "kv_get",
                             {"ns": "dash", "key": f"{wid}:phase"},
                             timeout=10.0)
    assert reply["found"]
    msg = json.loads(bytes(blobs[0]))
    assert msg["message"] == "phase 1 done"
    assert msg["dtype"] == "text"
    assert msg["task_id"]
    with pytest.raises(ValueError, match="dtype"):
        ray_tpu.show_in_dashboard("x", dtype="markdown")


def test_client_builder_surface():
    b = ray_tpu.ClientBuilder("ray://127.0.0.1:1")
    assert b.namespace("ns") is b
    assert b._namespace == "ns"


def test_log_once_and_node_ip(ray_shared):
    from ray_tpu import utils

    key = "compat-test-key"
    assert utils.log_once(key) is True
    assert utils.log_once(key) is False
    ip = utils.get_node_ip_address()
    assert ip and all(p.isdigit() for p in ip.split("."))


def test_list_named_actors(ray_shared):
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.options(name="compat-named", get_if_exists=True).remote()
    ray_tpu.get(a.ping.remote(), timeout=120)
    from ray_tpu import utils

    assert "compat-named" in utils.list_named_actors()
    rows = utils.list_named_actors(all_namespaces=True)
    assert {"namespace": "default", "name": "compat-named"} in rows
    ray_tpu.kill(a)


def test_register_serializer_roundtrip(ray_shared):
    from ray_tpu import utils

    class Opaque:
        """Unpicklable by construction."""

        def __init__(self, v):
            self.v = v
            self._lock = __import__("threading").Lock()

        def __reduce__(self):
            raise TypeError("not picklable")

    utils.register_serializer(Opaque, serializer=lambda o: o.v,
                              deserializer=Opaque)
    try:
        @ray_tpu.remote
        def probe(o):
            return o.v * 2

        assert ray_tpu.get(probe.remote(Opaque(21)), timeout=120) == 42
    finally:
        utils.deregister_serializer(Opaque)
    with pytest.raises(Exception):
        ray_tpu.put(Opaque(1))


def test_get_current_placement_group(ray_shared):
    from ray_tpu import utils

    pg = utils.placement_group([{"CPU": 1}], strategy="PACK",
                               name="compat-pg")
    assert pg.ready(timeout=60)

    @ray_tpu.remote(num_cpus=1)
    def where():
        cur = utils.get_current_placement_group()
        return cur.id if cur else None

    @ray_tpu.remote(num_cpus=1)
    def outside():
        cur = utils.get_current_placement_group()
        return cur.id if cur else None

    assert ray_tpu.get(
        where.options(placement_group=pg).remote(), timeout=120) == pg.id
    assert ray_tpu.get(outside.remote(), timeout=120) is None

    @ray_tpu.remote(num_cpus=1)
    class Member:
        def pg_id(self):
            cur = utils.get_current_placement_group()
            return cur.id if cur else None

    m = Member.options(placement_group=pg).remote()
    assert ray_tpu.get(m.pg_id.remote(), timeout=120) == pg.id
    # Named lookup resolves the same group.
    assert utils.get_placement_group("compat-pg").id == pg.id
    ray_tpu.kill(m)
    utils.remove_placement_group(pg)


def test_runtime_context_extras(ray_shared):
    from ray_tpu import utils

    pg = utils.placement_group([{"CPU": 1}], name="rc-pg")
    assert pg.ready(timeout=60)

    @ray_tpu.remote(num_cpus=1)
    def probe():
        ctx = ray_tpu.get_runtime_context()
        return {"d": ctx.get(), "pg": ctx.get_placement_group_id(),
                "res": ctx.get_assigned_resources(),
                "accel": ctx.get_accelerator_ids(),
                "renv": ctx.get_runtime_env_string(),
                "gcs": ctx.gcs_address}

    out = ray_tpu.get(probe.options(placement_group=pg).remote(),
                      timeout=120)
    assert out["pg"] == pg.id
    assert out["res"].get("CPU") == 1
    assert out["accel"] == {"TPU": []}
    assert "job_id" in out["d"]
    assert out["gcs"]
    # Driver-side context: no task/actor fields, no PG.
    ctx = ray_tpu.get_runtime_context()
    assert ctx.get_placement_group_id() is None
    assert ctx.get_actor_name() is None
    utils.remove_placement_group(pg)


def test_runtime_context_actor_name(ray_shared):
    @ray_tpu.remote
    class Named:
        def my_name(self):
            return ray_tpu.get_runtime_context().get_actor_name()

    a = Named.options(name="rc-named", get_if_exists=True).remote()
    assert ray_tpu.get(a.my_name.remote(), timeout=120) == "rc-named"
    ray_tpu.kill(a)


def test_exception_hierarchy(ray_shared):
    """Reference-spelled exception names are the SAME classes (ray:
    exceptions.py), and the typed subclasses come from real raise
    sites: an except on either spelling catches both."""
    import ray_tpu.exceptions as ex

    assert ex.RayTaskError is ex.TaskError
    assert ex.RayActorError is ex.ActorError
    assert ex.RayError is ex.RayTpuError
    assert issubclass(ex.OutOfMemoryError, ex.WorkerCrashedError)
    assert issubclass(ex.OwnerDiedError, ex.ObjectLostError)
    assert ex.RayChannelError.__name__ == "ChannelError"

    @ray_tpu.remote
    def boom():
        raise ValueError("user error")

    with pytest.raises(ex.RayTaskError) as ei:
        ray_tpu.get(boom.remote(), timeout=120)
    assert isinstance(ei.value.cause, ValueError)
