"""Dry-run validation of the k8s deployment (deploy/k8s).

The reference ships compose + helm role wiring
(/root/reference/deploy/docker-compose/docker-compose.yaml:51-93,
hack/install-e2e-test.sh); this validates the same invariants for the TPU
nodepool manifests without a cluster: YAML parses, every role is present,
the cross-role addresses (scheduler → manager, daemons → scheduler ring)
agree with the Services that serve them, and the daemon's ConfigMap ports
match its advertised container ports.
"""

from __future__ import annotations

import glob
import os

import yaml

K8S_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deploy", "k8s")


def _load_all() -> list[dict]:
    docs = []
    for path in sorted(glob.glob(os.path.join(K8S_DIR, "*.yaml"))):
        with open(path) as f:
            for doc in yaml.safe_load_all(f):
                if doc:
                    docs.append(doc)
    return docs


def _by_kind(docs, kind):
    return [d for d in docs if d.get("kind") == kind]


def _named(docs, kind, name):
    for d in _by_kind(docs, kind):
        if d["metadata"]["name"] == name:
            return d
    raise AssertionError(f"no {kind}/{name}")


def _container(doc, name=None):
    cs = doc["spec"]["template"]["spec"]["containers"]
    return cs[0] if name is None else next(c for c in cs if c["name"] == name)


def _service_ports(svc) -> dict[str, int]:
    return {p["name"]: p["port"] for p in svc["spec"]["ports"]}


class TestManifests:
    def setup_method(self):
        self.docs = _load_all()

    def test_all_roles_present(self):
        kinds = {(d["kind"], d.get("metadata", {}).get("name"))
                 for d in self.docs}
        for want in [("Namespace", "dragonfly-system"),
                     ("Deployment", "manager"),
                     ("StatefulSet", "scheduler"),
                     ("StatefulSet", "seed-peer"),
                     ("DaemonSet", "daemon"),
                     ("Service", "manager"),
                     ("Service", "scheduler"),
                     ("Service", "seed-peer"),
                     ("ConfigMap", "daemon-config")]:
            assert want in kinds, f"missing {want}"

    def test_everything_namespaced(self):
        for d in self.docs:
            if d["kind"] in ("Namespace", "Kustomization"):
                continue
            assert d["metadata"].get("namespace") == "dragonfly-system", (
                d["kind"], d["metadata"]["name"])

    def test_scheduler_points_at_manager_service(self):
        sched = _named(self.docs, "StatefulSet", "scheduler")
        args = _container(sched)["args"]
        manager_ref = args[args.index("--manager") + 1]
        host, _, port = manager_ref.partition(":")
        svc = _named(self.docs, "Service", "manager")
        assert host == svc["metadata"]["name"]
        assert int(port) in _service_ports(svc).values()

    def test_daemons_point_at_scheduler_ring(self):
        svc = _named(self.docs, "Service", "scheduler")
        assert svc["spec"].get("clusterIP") == "None", "ring needs pod DNS"
        sched = _named(self.docs, "StatefulSet", "scheduler")
        replicas = sched["spec"]["replicas"]
        drpc_port = _service_ports(svc)["drpc"]
        for role, kind in [("seed-peer", "StatefulSet"),
                           ("daemon", "DaemonSet")]:
            args = _container(_named(self.docs, kind, role))["args"]
            ring = args[args.index("--scheduler") + 1].split(",")
            assert len(ring) == replicas, (role, ring)
            for i, member in enumerate(ring):
                host, _, port = member.partition(":")
                assert host.startswith(f"scheduler-{i}.scheduler"), member
                assert int(port) == drpc_port, member

    def test_daemon_config_ports_match_container_ports(self):
        cm = _named(self.docs, "ConfigMap", "daemon-config")
        cfg = yaml.safe_load(cm["data"]["daemon.yaml"])
        ds = _named(self.docs, "DaemonSet", "daemon")
        ports = {p["name"]: p for p in _container(ds)["ports"]}
        assert cfg["download"]["peer_port"] == ports["peer"]["containerPort"]
        assert cfg["upload"]["port"] == ports["upload"]["containerPort"]
        # hostNetwork peers: hostPort must equal containerPort.
        for p in ports.values():
            assert p.get("hostPort", p["containerPort"]) == p["containerPort"]
        assert ds["spec"]["template"]["spec"].get("hostNetwork") is True

    def test_daemon_config_is_loadable_by_daemon(self):
        from dragonfly2_tpu.daemon.config import DaemonConfig

        cm = _named(self.docs, "ConfigMap", "daemon-config")
        cfg = DaemonConfig.from_dict(yaml.safe_load(cm["data"]["daemon.yaml"]))
        assert cfg.download.peer_port == 65000
        assert cfg.upload.port == 65002
        assert cfg.tpu_sink.enabled is True
        # A key this release no longer has (a field an older one read) is
        # passed over like any unknown key: the file still loads.
        doc = yaml.safe_load(cm["data"]["daemon.yaml"])
        doc["tpu_sink"]["a_field_of_an_older_release"] = [2, 2]
        old = DaemonConfig.from_dict(doc)
        assert old.tpu_sink == cfg.tpu_sink
        assert not hasattr(old.tpu_sink, "a_field_of_an_older_release")
        args = _container(_named(self.docs, "DaemonSet", "daemon"))["args"]
        assert args[args.index("--config") + 1] == "/etc/dragonfly/daemon.yaml"

    def test_daemon_pinned_to_tpu_nodepool(self):
        ds = _named(self.docs, "DaemonSet", "daemon")
        spec = ds["spec"]["template"]["spec"]
        assert any("tpu" in str(v) for v in
                   (spec.get("nodeSelector") or {}).values())
        assert any("tpu" in (t.get("key") or "")
                   for t in spec.get("tolerations") or [])

    def test_sqlite_owners_never_scale_past_their_storage(self):
        mgr = _named(self.docs, "Deployment", "manager")
        assert mgr["spec"]["replicas"] == 1
        assert mgr["spec"]["strategy"]["type"] == "Recreate"
        seed = _named(self.docs, "StatefulSet", "seed-peer")
        assert seed["spec"].get("volumeClaimTemplates"), \
            "seeds need per-pod stores"

    def test_kustomization_lists_every_file(self):
        kust = [d for d in self.docs if d.get("kind") == "Kustomization"]
        assert kust, "kustomization.yaml missing"
        listed = set(kust[0]["resources"])
        have = {os.path.basename(p)
                for p in glob.glob(os.path.join(K8S_DIR, "*.yaml"))}
        assert listed == have - {"kustomization.yaml"}, (listed, have)
