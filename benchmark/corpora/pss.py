"""The pss-admit corpus: Pod Security Standards admission for a multi-tenant
cluster, and the AdmissionReviews its apiserver sends during a deploy.

Policies (about 620 at 300 tenants), in three files:

  pss.cedar      one ``forbid`` per control of the Baseline and Restricted
                 profiles (kubernetes.io/docs/concepts/security/
                 pod-security-standards, the control tables) that Cedar can
                 state on pod-level fields, per workload kind (``core::v1::Pod``
                 on ``resource.spec``, ``apps::v1::Deployment`` on
                 ``resource.spec.template.spec``) for create and update. The
                 namespaces a control applies to are a set literal tested with
                 ``.contains(resource.metadata.namespace)``: Baseline controls
                 hold in baseline and restricted namespaces, Restricted
                 controls in restricted ones, nothing in privileged ones.
  tenants.cedar  per tenant, after demo/admission-policy.yaml: a Deployment
                 written by a member of the tenant's group carries the label
                 ``owner`` = the member's name, and a member writes nowhere
                 but in the tenant's namespace.
  demo-*.cedar   the upstream project's two demo admission policies
                 (``corpora/data/``, as the synth corpus carries them).

The controls that are left out, and why, are the configuration's
``departures`` (``configs/pss-admit.json``). Every attribute access is
guarded with ``has``, so no policy errs on any review made here
(``tests/benchmark_tests/test_benchmark_admission.py`` holds that).

Reviews: Pod and Deployment objects as an apiserver sends them after
defaulting, in a fixed cycle of (operation, size class) pairs whose order
the seed draws, so that every window holds the same mix of operations and
sizes; everything else of a review is drawn per request. It imports
nothing of the program.
"""

from __future__ import annotations

import copy
import pathlib
import random

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEMO_FILES = ("demo-require-owner-label.cedar", "demo-combined-authz-admission.cedar")

ACTION = "k8s::admission::Action"
LEVELS = (("privileged", 0.1), ("baseline", 0.6), ("restricted", 0.3))
SKIPPED_NAMESPACE = "kube-system"
REPLICASET_CONTROLLER = "system:serviceaccount:kube-system:replicaset-controller"
FURTHER_GROUPS = ("system:authenticated", "oidc:engineering", "oidc:on-call",
                  "oidc:deployers", "oidc:contractors", "oidc:platform-readers")

# (containers, env entries a container), smallest object to largest: adjacent
# classes lie close, so that a percentile never sits on a cliff between two
SIZE_CLASSES = ((1, 4), (1, 10), (2, 6), (2, 11), (3, 8), (3, 13),
                (4, 9), (4, 14), (5, 10), (5, 14), (6, 10), (6, 13))
# CREATE 0.6, UPDATE 0.3, DELETE 0.1
OPERATIONS = ("CREATE",) * 6 + ("UPDATE",) * 3 + ("DELETE",)

SELINUX_ALLOWED = ("container_t", "container_init_t", "container_kvm_t", "container_engine_t")


# --------------------------------------------------------------- policies

def _set_literal(names) -> str:
    return "[" + ", ".join(f'"{n}"' for n in names) + "]"


def _has_chain(root: str, path: tuple) -> str:
    """``root has a && root.a has b && ...`` down the whole of ``path``."""
    parts, node = [], root
    for attr in path:
        parts.append(f"{node} has {attr}")
        node = f"{node}.{attr}"
    return " && ".join(parts)


def _controls(spec: str) -> list:
    """(level, name, guard, "when" or "unless", condition) for each control
    stated on the pod spec ``spec``; every access guarded with ``has``."""
    sc = f"{spec}.securityContext"
    se = f"{sc}.seLinuxOptions"
    has_spec = _has_chain("resource", tuple(spec.split(".")[1:]))
    has_sc = f"{has_spec} && {spec} has securityContext"
    allowed = _set_literal(("",) + SELINUX_ALLOWED)
    out = [
        ("baseline", f"host-{field[4:].lower()}", has_spec, "when",
         f"{spec} has {field} && {spec}.{field} == true")
        for field in ("hostNetwork", "hostPID", "hostIPC")
    ]
    for level, name, holds, path, test in (
        ("baseline", "host-process", "when", ("windowsOptions", "hostProcess"),
         f"{sc}.windowsOptions.hostProcess == true"),
        ("baseline", "selinux-type", "when", ("seLinuxOptions", "type"),
         f"!{allowed}.contains({se}.type)"),
        ("baseline", "selinux-user", "when", ("seLinuxOptions", "user"), f'{se}.user != ""'),
        ("baseline", "selinux-role", "when", ("seLinuxOptions", "role"), f'{se}.role != ""'),
        ("baseline", "seccomp-unconfined", "when", ("seccompProfile", "type"),
         f'{sc}.seccompProfile.type == "Unconfined"'),
        # Restricted: the first and the last have to hold, the second breaks
        ("restricted", "run-as-non-root", "unless", ("runAsNonRoot",),
         f"{sc}.runAsNonRoot == true"),
        ("restricted", "run-as-user-0", "when", ("runAsUser",), f"{sc}.runAsUser == 0"),
        ("restricted", "seccomp-profile", "unless", ("seccompProfile", "type"),
         f'["RuntimeDefault", "Localhost"].contains({sc}.seccompProfile.type)'),
    ):
        out.append((level, name, has_sc, holds, f"{_has_chain(sc, path)} && {test}"))
    return out


def pss_policies(baseline_ns: list, restricted_ns: list) -> str:
    """The PSS forbids, per workload kind; Baseline's hold in baseline and
    restricted namespaces alike."""
    held = {"baseline": _set_literal(sorted(baseline_ns + restricted_ns)),
            "restricted": _set_literal(sorted(restricted_ns))}
    out = []
    for rtype, spec in (("core::v1::Pod", "resource.spec"),
                        ("apps::v1::Deployment", "resource.spec.template.spec")):
        for level, name, guard, holds, condition in _controls(spec):
            out.append(
                f"// {level}/{name}\n"
                f'forbid (principal, action in [{ACTION}::"create", {ACTION}::"update"], '
                f"resource is {rtype}) when {{ resource has metadata && "
                f"resource.metadata has namespace && "
                f"{held[level]}.contains(resource.metadata.namespace) }} "
                f"{holds} {{ {guard} && {condition} }};")
    return "\n".join(out) + "\n"


def tenant_policies(tenants: list) -> str:
    out = []
    for t in tenants:
        member = f'principal is k8s::User in k8s::Group::"{t["group"]}"'
        out.append(
            f'forbid ({member}, action in [{ACTION}::"create", {ACTION}::"update"], '
            "resource is apps::v1::Deployment) unless { "
            "resource has metadata && resource.metadata has labels && "
            'resource.metadata.labels.contains({key: "owner", value: principal.name}) };'
        )
        out.append(
            f'forbid ({member}, action in [{ACTION}::"create", {ACTION}::"update", '
            f'{ACTION}::"delete"], resource) unless {{ '
            "resource has metadata && resource.metadata has namespace && "
            f'resource.metadata.namespace == "{t["namespace"]}" }};'
        )
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------- objects

def _hex(rng: random.Random, n: int) -> str:
    return "".join(rng.choice("0123456789abcdef") for _ in range(n))


def _uuid(rng: random.Random) -> str:
    return "-".join(_hex(rng, n) for n in (8, 4, 4, 4, 12))


def _probe(path: str, port: int, delay: int) -> dict:
    return {"httpGet": {"path": path, "port": port, "scheme": "HTTP"},
            "initialDelaySeconds": delay, "timeoutSeconds": 1, "periodSeconds": 10,
            "successThreshold": 1, "failureThreshold": 3}


def _env(rng: random.Random, app: str, j: int) -> dict:
    name = f"{app.upper().replace('-', '_')}_{rng.choice(('URL', 'MODE', 'LIMIT', 'REGION', 'FLAG'))}_{j}"
    form = rng.random()
    if form < 0.7:
        return {"name": name, "value": rng.choice((
            "", "true", "info", f"https://{app}.internal.example.com:8443/v{j}",
            f"{rng.randint(1, 65535)}", "eu-west-1", f"{_hex(rng, 24)}"))}
    if form < 0.85:
        return {"name": name, "valueFrom": {"fieldRef": {
            "apiVersion": "v1",
            "fieldPath": rng.choice(("metadata.name", "metadata.namespace", "status.podIP"))}}}
    return {"name": name, "valueFrom": {"secretKeyRef": {
        "name": f"{app}-credentials", "key": f"key-{j}"}}}


def _container(rng: random.Random, app: str, k: int, n_env: int, volumes: list) -> dict:
    port = 8080 + k
    c = {
        "name": app if k == 0 else f"{app}-sidecar-{k}",
        "image": f"registry.example.com/{app}/{'server' if k == 0 else 'agent'}:"
                 f"{rng.randint(1, 9)}.{rng.randint(0, 30)}.{rng.randint(0, 99)}",
        "args": [f"--listen=:{port}", f"--log-level={rng.choice(('info', 'debug', 'warn'))}",
                 f"--shard={rng.randint(0, 63)}"],
        "ports": [{"name": "http" if k == 0 else f"http-{k}", "containerPort": port,
                   "protocol": "TCP"}],
        "env": [_env(rng, app, j) for j in range(n_env)],
        "resources": {
            "limits": {"cpu": f"{rng.choice((250, 500, 1000, 2000))}m",
                       "memory": f"{rng.choice((128, 256, 512, 1024))}Mi"},
            "requests": {"cpu": f"{rng.choice((50, 100, 250))}m",
                         "memory": f"{rng.choice((64, 128, 256))}Mi"}},
        "volumeMounts": [
            {"name": v["name"], "mountPath": f"/var/run/{v['name']}", "readOnly": True}
            for v in volumes[: rng.randint(1, len(volumes))]],
        "livenessProbe": _probe("/healthz", port, rng.randint(5, 30)),
        "readinessProbe": _probe("/readyz", port, rng.randint(1, 10)),
        "terminationMessagePath": "/dev/termination-log",
        "terminationMessagePolicy": "File",
        "imagePullPolicy": "IfNotPresent",
        "securityContext": {"capabilities": {"drop": ["ALL"]},
                            "readOnlyRootFilesystem": True,
                            "allowPrivilegeEscalation": False},
    }
    if rng.random() < 0.3:
        c["command"] = [f"/usr/local/bin/{app}"]
    return c


def _pod_security_context(rng: random.Random, level: str) -> dict:
    """A pod-level securityContext that passes the namespace's level."""
    if level == "restricted":
        sc = {"runAsNonRoot": True, "seccompProfile": {
            "type": rng.choice(("RuntimeDefault", "RuntimeDefault", "Localhost"))}}
        if sc["seccompProfile"]["type"] == "Localhost":
            sc["seccompProfile"]["localhostProfile"] = "profiles/audit.json"
        if rng.random() < 0.7:
            sc["runAsUser"] = rng.choice((1000, 10001, 65532))
    else:
        # baseline forbids none of these, root included
        sc = rng.choice((
            {}, {}, {"runAsUser": 1000}, {"runAsUser": 0},
            {"seccompProfile": {"type": "RuntimeDefault"}},
            {"runAsNonRoot": True, "runAsUser": 65532,
             "seccompProfile": {"type": "RuntimeDefault"}},
        ))
        sc = copy.deepcopy(sc)
    if rng.random() < 0.4:
        sc["fsGroup"] = rng.choice((1, 2000, 65532))
    if rng.random() < 0.15:
        sc["seLinuxOptions"] = {"type": rng.choice(SELINUX_ALLOWED),
                                "level": f"s0:c{rng.randint(1, 500)},c{rng.randint(501, 1023)}"}
    return sc


# each breaks one control of pss_policies (a few break a second one too:
# the reference, not this table, says what an answer names)
def _break(rng: random.Random, spec: dict, control: str) -> None:
    sc = spec.setdefault("securityContext", {})
    if control in ("hostNetwork", "hostPID", "hostIPC"):
        spec[control] = True
    elif control == "hostProcess":
        sc["windowsOptions"] = {"hostProcess": True, "runAsUserName": "NT AUTHORITY\\SYSTEM"}
    elif control == "selinux-type":
        sc.setdefault("seLinuxOptions", {})["type"] = "spc_t"
    elif control == "selinux-user":
        sc.setdefault("seLinuxOptions", {})["user"] = "system_u"
    elif control == "selinux-role":
        sc.setdefault("seLinuxOptions", {})["role"] = "system_r"
    elif control == "seccomp-unconfined":
        sc["seccompProfile"] = {"type": "Unconfined"}
    elif control == "run-as-non-root":
        if rng.random() < 0.5:
            sc["runAsNonRoot"] = False
        else:
            sc.pop("runAsNonRoot", None)
    elif control == "run-as-user-0":
        sc["runAsUser"] = 0
    elif control == "seccomp-profile":
        sc.pop("seccompProfile", None)
    else:
        raise ValueError(control)


BASELINE_BREAKS = ("hostNetwork", "hostPID", "hostIPC", "hostProcess", "selinux-type",
                   "selinux-user", "selinux-role", "seccomp-unconfined")
RESTRICTED_BREAKS = ("run-as-non-root", "run-as-user-0", "seccomp-profile")


def _labels(rng: random.Random, app: str, n: int) -> dict:
    labels = {"app.kubernetes.io/name": app,
              "app.kubernetes.io/instance": f"{app}-{rng.choice(('prod', 'canary', 'staging'))}",
              "app.kubernetes.io/version": f"{rng.randint(1, 9)}.{rng.randint(0, 30)}",
              "app.kubernetes.io/managed-by": "Helm"}
    for j in range(n - len(labels)):
        labels[f"example.com/{rng.choice(('tier', 'team', 'cost-center', 'track'))}-{j}"] = (
            rng.choice(("web", "backend", "batch", "payments", "cc-1042", "stable")))
    return labels


def _annotations(rng: random.Random, n: int) -> dict:
    pool = (
        ("prometheus.io/scrape", "true"), ("prometheus.io/port", "9090"),
        ("checksum/config", None), ("example.com/owner-contact", "platform@example.com"),
        ("kubectl.kubernetes.io/restartedAt", "2026-03-01T08:00:00Z"),
        ("example.com/change-ticket", None),
    )
    out = {}
    for key, value in rng.sample(pool, n):
        out[key] = value if value is not None else _hex(rng, 64)
    return out


def pod_spec(rng: random.Random, app: str, size: tuple, level: str) -> dict:
    """A pod spec as the apiserver's defaulting leaves it."""
    n_containers, n_env = size
    volumes = [{"name": f"{app}-config",
                "configMap": {"name": f"{app}-config", "defaultMode": 420}},
               {"name": "tmp", "emptyDir": {}}]
    if rng.random() < 0.5:
        volumes.append({"name": f"{app}-tls",
                        "secret": {"secretName": f"{app}-tls", "defaultMode": 420}})
    volumes.append({"name": f"kube-api-access-{_hex(rng, 5)}", "projected": {
        "defaultMode": 420, "sources": [
            {"serviceAccountToken": {"expirationSeconds": 3607, "path": "token"}},
            {"configMap": {"name": "kube-root-ca.crt",
                           "items": [{"key": "ca.crt", "path": "ca.crt"}]}},
            {"downwardAPI": {"items": [{"path": "namespace", "fieldRef": {
                "apiVersion": "v1", "fieldPath": "metadata.namespace"}}]}}]}})
    spec = {
        "volumes": volumes,
        "containers": [_container(rng, app, k, n_env, volumes) for k in range(n_containers)],
        "restartPolicy": "Always",
        "terminationGracePeriodSeconds": 30,
        "dnsPolicy": "ClusterFirst",
        "serviceAccountName": app,
        "serviceAccount": app,
        "securityContext": _pod_security_context(rng, level),
        "schedulerName": "default-scheduler",
        "tolerations": [
            {"key": "node.kubernetes.io/not-ready", "operator": "Exists",
             "effect": "NoExecute", "tolerationSeconds": 300},
            {"key": "node.kubernetes.io/unreachable", "operator": "Exists",
             "effect": "NoExecute", "tolerationSeconds": 300}],
        "priority": 0,
        "enableServiceLinks": True,
        "preemptionPolicy": "PreemptLowerPriority",
    }
    if rng.random() < 0.3:
        spec["nodeSelector"] = {"kubernetes.io/os": "linux",
                                "example.com/pool": rng.choice(("general", "compute", "memory"))}
    return spec


def _pod_status(rng: random.Random, spec: dict) -> dict:
    """The status of a running pod: what an UPDATE or a DELETE carries."""
    ip = f"10.{rng.randint(0, 255)}.{rng.randint(0, 255)}.{rng.randint(2, 254)}"
    host = f"192.168.{rng.randint(0, 255)}.{rng.randint(2, 254)}"
    started = "2026-03-01T08:00:05Z"
    return {
        "phase": "Running",
        "conditions": [
            {"type": t, "status": "True", "lastProbeTime": None, "lastTransitionTime": started}
            for t in ("Initialized", "Ready", "ContainersReady", "PodScheduled")],
        "hostIP": host, "hostIPs": [{"ip": host}],
        "podIP": ip, "podIPs": [{"ip": ip}],
        "startTime": started,
        "containerStatuses": [
            {"name": c["name"], "state": {"running": {"startedAt": started}}, "lastState": {},
             "ready": True, "restartCount": rng.randint(0, 3), "image": c["image"],
             "imageID": f"registry.example.com/{c['name']}@sha256:{_hex(rng, 64)}",
             "containerID": f"containerd://{_hex(rng, 64)}", "started": True}
            for c in spec["containers"]],
        "qosClass": "Burstable",
    }


class Corpus:
    def __init__(self, params: dict, seed: int):
        rng = random.Random(f"{seed}:pss")
        n = int(params["tenants"])
        levels = []
        for level, share in LEVELS:
            levels += [level] * int(round(share * n))
        levels = (levels + ["baseline"] * n)[:n]
        rng.shuffle(levels)
        self.tenants = [
            {"namespace": f"tenant-{i:03d}", "group": f"tenant-{i:03d}:developers",
             "level": levels[i]} for i in range(n)]
        by_level = {level: [t["namespace"] for t in self.tenants if t["level"] == level]
                    for level, _ in LEVELS}
        self.enforced = [i for i, t in enumerate(self.tenants) if t["level"] != "privileged"]
        self.files = {
            "pss.cedar": pss_policies(by_level["baseline"], by_level["restricted"]),
            "tenants.cedar": tenant_policies(self.tenants),
            **{name: (DATA / name).read_text() for name in DEMO_FILES},
        }
        # the fixed cycle: every (operation, size class) pair once, in the
        # seed's order; request i takes entry i of it, so any stretch of a
        # stream holds the same mix
        self.cycle = [(op, size) for op in OPERATIONS for size in SIZE_CLASSES]
        rng.shuffle(self.cycle)
        self._next = 0

    # -- one review
    def spec(self, rng: random.Random, aimed_share: float) -> dict:
        """One AdmissionReview ``request``. ``aimed_share`` of them break one
        to three controls, the owner rule or the namespace rule, and are
        denied wherever a policy holds for them; the rest pass every policy
        and reach the allow-all tier."""
        op, size = self.cycle[self._next % len(self.cycle)]
        self._next += 1
        workload = "Pod" if rng.random() < 0.7 else "Deployment"
        aimed = rng.random() < aimed_share
        by_controller = workload == "Pod" and op == "CREATE" and rng.random() < 0.7
        in_skipped = rng.random() < 0.05
        # what an aimed review breaks, of what can be broken in it
        choices = []
        if op != "DELETE":
            choices += ["pss"] * 6
        if not by_controller:
            choices += ["namespace"] * (2 if op != "DELETE" else 1)
            if workload == "Deployment" and op != "DELETE":
                choices += ["owner"] * 3
        breaks = rng.choice(choices) if aimed else None
        # the tenant; an aimed PSS review goes where a level is enforced
        t = self.tenants[rng.choice(self.enforced) if breaks == "pss"
                         else rng.randrange(len(self.tenants))]
        namespace, level = t["namespace"], t["level"]
        if by_controller:
            user = {"username": REPLICASET_CONTROLLER, "uid": _uuid(rng),
                    "groups": ["system:serviceaccounts", "system:serviceaccounts:kube-system",
                               "system:authenticated"],
                    "extra": {"authentication.kubernetes.io/credential-id":
                              [f"JTI={_uuid(rng)}"]}}
        else:
            name = f"{rng.choice(('alex', 'sam', 'kim', 'ravi', 'noor', 'lena'))}-{rng.randint(0, 99)}"
            user = {"username": name,
                    "groups": [t["group"]] + rng.sample(FURTHER_GROUPS, rng.randint(2, 4))}
        if breaks == "namespace":
            other = self.tenants[rng.randrange(len(self.tenants))]
            namespace, level = other["namespace"], other["level"]
        if in_skipped:
            namespace = SKIPPED_NAMESPACE
        app = f"{rng.choice(('checkout', 'ledger', 'search', 'ingest', 'notify', 'gateway'))}-{rng.randint(0, 999)}"
        pod = pod_spec(rng, app, size, level)
        labels = _labels(rng, app, rng.randint(4, 12))
        meta = {"name": app, "namespace": namespace, "labels": labels,
                "annotations": _annotations(rng, rng.randint(2, 6))}
        if workload == "Pod":
            rs = f"{app}-{_hex(rng, 9)}"
            meta["generateName"] = rs + "-"
            meta["labels"]["pod-template-hash"] = rs.rsplit("-", 1)[1]
            meta["ownerReferences"] = [{"apiVersion": "apps/v1", "kind": "ReplicaSet", "name": rs,
                                        "uid": _uuid(rng), "controller": True,
                                        "blockOwnerDeletion": True}]
            obj = {"apiVersion": "v1", "kind": "Pod", "metadata": meta, "spec": pod, "status": {}}
            gvk = {"group": "", "version": "v1", "kind": "Pod"}
            gvr = {"group": "", "version": "v1", "resource": "pods"}
        else:
            if breaks != "owner":
                meta["labels"]["owner"] = user["username"]
            elif rng.random() < 0.5:
                meta["labels"]["owner"] = "someone-else"
            match = {"app.kubernetes.io/name": app,
                     "app.kubernetes.io/instance": labels["app.kubernetes.io/instance"]}
            meta["generation"] = 1
            obj = {"apiVersion": "apps/v1", "kind": "Deployment", "metadata": meta,
                   "spec": {"replicas": rng.randint(1, 12),
                            "selector": {"matchLabels": match},
                            "template": {"metadata": {"creationTimestamp": None,
                                                      "labels": dict(match, **{
                                                          "example.com/tier-0": "web"})},
                                         "spec": pod},
                            "strategy": {"type": "RollingUpdate", "rollingUpdate": {
                                "maxUnavailable": "25%", "maxSurge": "25%"}},
                            "revisionHistoryLimit": 10, "progressDeadlineSeconds": 600},
                   "status": {}}
            gvk = {"group": "apps", "version": "v1", "kind": "Deployment"}
            gvr = {"group": "apps", "version": "v1", "resource": "deployments"}
        old = None
        if op != "CREATE":
            # what the apiserver holds: the object before this write
            old = copy.deepcopy(obj)
            old["metadata"].update(uid=_uuid(rng), resourceVersion=str(rng.randint(10**5, 10**8)),
                                   creationTimestamp="2026-03-01T08:00:00Z")
            if workload == "Pod":
                old["spec"]["nodeName"] = f"node-{rng.randint(0, 199)}"
                old["status"] = _pod_status(rng, pod)
            else:
                n = obj["spec"]["replicas"]
                old["status"] = {"observedGeneration": 1, "replicas": n, "updatedReplicas": n,
                                 "readyReplicas": n, "availableReplicas": n, "conditions": [
                                     {"type": "Available", "status": "True",
                                      "reason": "MinimumReplicasAvailable",
                                      "lastUpdateTime": "2026-03-01T08:01:00Z",
                                      "lastTransitionTime": "2026-03-01T08:01:00Z"}]}
        if op == "UPDATE":
            obj = copy.deepcopy(old)
            if workload == "Deployment":
                obj["metadata"]["generation"] = 2
            obj["metadata"]["annotations"]["example.com/change-ticket"] = _hex(rng, 16)
            target = obj["spec"] if workload == "Pod" else obj["spec"]["template"]["spec"]
            target["containers"][0]["image"] = target["containers"][0]["image"] + "-hotfix"
        if breaks == "pss":
            target = obj["spec"] if workload == "Pod" else obj["spec"]["template"]["spec"]
            pool = BASELINE_BREAKS + (RESTRICTED_BREAKS * 2 if level == "restricted" else ())
            for control in rng.sample(pool, rng.randint(1, 3)):
                _break(rng, target, control)
        kind_options = {"CREATE": "CreateOptions", "UPDATE": "UpdateOptions",
                        "DELETE": "DeleteOptions"}[op]
        options = {"kind": kind_options, "apiVersion": "meta.k8s.io/v1"}
        if op == "DELETE":
            options["propagationPolicy"] = "Background"
        else:
            options["fieldManager"] = ("kube-controller-manager" if by_controller
                                       else "kubectl-client-side-apply")
            if not by_controller:
                options["fieldValidation"] = "Strict"
        return {
            "uid": _uuid(rng), "kind": gvk, "resource": gvr,
            "requestKind": gvk, "requestResource": gvr,
            "name": app, "namespace": namespace, "operation": op, "userInfo": user,
            "object": None if op == "DELETE" else obj,
            "oldObject": old, "dryRun": False, "options": options,
        }


def build(params: dict, seed: int) -> Corpus:
    return Corpus(params, seed)
