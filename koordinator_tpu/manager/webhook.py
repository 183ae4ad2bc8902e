"""Admission webhooks (reference: ``pkg/webhook/`` — pod mutating
``pod/mutating/cluster_colocation_profile.go`` + ``extended_resource_spec.go``,
pod validating ``pod/validating/``, quota evaluation ``quotaevaluate/``,
ConfigMap validation ``cm/``).

Pods cross this boundary as plain nested dicts (the admission JSON shape);
mutators return the changed pod, validators return error lists.
"""

from __future__ import annotations

import hashlib
from typing import Mapping, Optional

from koordinator_tpu import timeline
from koordinator_tpu.api import crds, extension as ext
from koordinator_tpu.api.priority import (
    PRIORITY_BATCH_MAX, PRIORITY_BATCH_MIN, PriorityClass, priority_class_of,
)
from koordinator_tpu.api.qos import QoSClass
from koordinator_tpu.manager.sloconfig import validate_config_data  # re-export

__all__ = [
    "PodMutatingWebhook", "PodValidatingWebhook", "QuotaEvaluator",
    "validate_config_data",
]


def _meta(pod: dict) -> dict:
    return pod.setdefault("metadata", {})


def _labels(pod: dict) -> dict:
    return _meta(pod).setdefault("labels", {})


def _annotations(pod: dict) -> dict:
    return _meta(pod).setdefault("annotations", {})


def _selector_matches(selector: Mapping[str, str], labels: Mapping[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in selector.items())


def _stable_fraction(pod: dict) -> float:
    """Deterministic [0,1) hash of the pod identity for canary probability."""
    meta = _meta(pod)
    key = f"{meta.get('namespace', '')}/{meta.get('name', '')}/{meta.get('uid', '')}"
    digest = hashlib.sha256(key.encode()).digest()
    return int.from_bytes(digest[:8], "big") / 2**64


class PodMutatingWebhook:
    """ClusterColocationProfile injection + BE extended-resource translation."""

    def __init__(self, profiles: list[crds.ClusterColocationProfile] | None = None):
        self.profiles = list(profiles or [])

    def set_profiles(self, profiles: list[crds.ClusterColocationProfile]) -> None:
        self.profiles = list(profiles)

    def mutate(self, pod: dict,
               namespace_labels: Mapping[str, str] | None = None) -> dict:
        """Admission mutate: returns the (mutated) pod dict."""
        t0 = timeline.RECORDER.open("colo.admit")
        try:
            for profile in self.profiles:
                if not self._profile_matches(profile, pod,
                                             namespace_labels or {}):
                    continue
                self._apply_profile(profile, pod)
            self._translate_batch_resources(pod)
        finally:
            timeline.RECORDER.close(t0, "host_other")
        return pod

    def _profile_matches(self, profile: crds.ClusterColocationProfile,
                         pod: dict, ns_labels: Mapping[str, str]) -> bool:
        if profile.namespace_selector and not _selector_matches(
            profile.namespace_selector, ns_labels
        ):
            return False
        if profile.pod_selector and not _selector_matches(
            profile.pod_selector, _labels(pod)
        ):
            return False
        if profile.patch_probability < 1.0:
            return _stable_fraction(pod) < profile.patch_probability
        return True

    def _apply_profile(self, profile: crds.ClusterColocationProfile, pod: dict):
        labels = _labels(pod)
        annotations = _annotations(pod)
        if profile.qos_class:
            labels[ext.LABEL_POD_QOS] = profile.qos_class
        if profile.koordinator_priority is not None:
            pod.setdefault("spec", {})["priority"] = profile.koordinator_priority
        if profile.priority_class_name:
            pod.setdefault("spec", {})["priorityClassName"] = (
                profile.priority_class_name
            )
        if profile.scheduler_name:
            pod.setdefault("spec", {})["schedulerName"] = profile.scheduler_name
        labels.update(profile.labels)
        annotations.update(profile.annotations)

    def _translate_batch_resources(self, pod: dict) -> None:
        """extended_resource_spec.go: BE pods' native cpu/memory requests are
        rewritten to batch-cpu (milli) / batch-memory (bytes) so kubelet
        accounts them against the overcommitted pool."""
        qos = QoSClass.parse(_labels(pod).get(ext.LABEL_POD_QOS, ""))
        priority = pod.get("spec", {}).get("priority")
        if qos is not QoSClass.BE:
            return
        if priority is not None and not (
            PRIORITY_BATCH_MIN <= priority <= PRIORITY_BATCH_MAX
        ):
            return
        for container in pod.get("spec", {}).get("containers", []):
            resources = container.setdefault("resources", {})
            for section in ("requests", "limits"):
                values = resources.get(section)
                if not values:
                    continue
                if "cpu" in values:
                    values[ext.RESOURCE_BATCH_CPU] = _cpu_to_milli(values.pop("cpu"))
                if "memory" in values:
                    values[ext.RESOURCE_BATCH_MEMORY] = _mem_to_bytes(
                        values.pop("memory")
                    )


def _cpu_to_milli(value) -> int:
    if isinstance(value, (int, float)):
        return int(value * 1000)
    s = str(value)
    if s.endswith("m"):
        return int(s[:-1])
    return int(float(s) * 1000)


_MEM_SUFFIX = {
    "Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40,
    "K": 10**3, "M": 10**6, "G": 10**9, "T": 10**12,
}


def _mem_to_bytes(value) -> int:
    if isinstance(value, (int, float)):
        return int(value)
    s = str(value)
    for suffix, mult in _MEM_SUFFIX.items():
        if s.endswith(suffix):
            return int(float(s[: -len(suffix)]) * mult)
    return int(float(s))


#: QoS class -> allowed priority bands (validating webhook compatibility
#: matrix, pod/validating/cluster_colocation_profile.go)
QOS_PRIORITY_COMPAT: dict[QoSClass, tuple[PriorityClass, ...]] = {
    QoSClass.LSE: (PriorityClass.PROD, PriorityClass.NONE),
    QoSClass.LSR: (PriorityClass.PROD, PriorityClass.NONE),
    QoSClass.LS: (PriorityClass.PROD, PriorityClass.MID, PriorityClass.NONE),
    QoSClass.BE: (PriorityClass.MID, PriorityClass.BATCH, PriorityClass.FREE,
                  PriorityClass.NONE),
    QoSClass.SYSTEM: (PriorityClass.NONE,),
    QoSClass.NONE: tuple(PriorityClass),
}


class PodValidatingWebhook:
    def validate(self, pod: dict) -> list[str]:
        errors: list[str] = []
        labels = _labels(pod)
        qos = QoSClass.parse(labels.get(ext.LABEL_POD_QOS, ""))
        priority = pod.get("spec", {}).get("priority")
        band = priority_class_of(priority) if priority is not None else PriorityClass.NONE
        allowed = QOS_PRIORITY_COMPAT.get(qos, tuple(PriorityClass))
        if band not in allowed:
            errors.append(
                f"qosClass {qos.name} incompatible with priority band {band.name}"
            )
        errors.extend(self._verify_batch_resources(pod, qos))
        return errors

    def _verify_batch_resources(self, pod: dict, qos: QoSClass) -> list[str]:
        """verify_*.go: batch resources must come as matched request/limit and
        never mixed with native cpu/memory in the same container."""
        errors = []
        for container in pod.get("spec", {}).get("containers", []):
            resources = container.get("resources", {})
            requests = resources.get("requests", {})
            limits = resources.get("limits", {})
            has_batch = any(
                k in requests or k in limits
                for k in (ext.RESOURCE_BATCH_CPU, ext.RESOURCE_BATCH_MEMORY)
            )
            has_native = "cpu" in requests or "memory" in requests
            if has_batch and has_native:
                errors.append(
                    f"container {container.get('name', '?')}: batch and native "
                    "resources must not be mixed"
                )
            for resource, label in ((ext.RESOURCE_BATCH_CPU, "batch-cpu"),
                                    (ext.RESOURCE_BATCH_MEMORY, "batch-memory")):
                req_b = requests.get(resource)
                lim_b = limits.get(resource)
                if req_b is not None and lim_b is not None and req_b != lim_b:
                    errors.append(
                        f"container {container.get('name', '?')}: {label} "
                        "request must equal limit"
                    )
        return errors


class MultiQuotaTreeAffinity:
    """Multi-quota-tree node affinity injection.

    Reference: ``pkg/webhook/pod/mutating/multi_quota_tree_affinity.go`` — at
    pod CREATE, if the pod's quota (label, else namespace) belongs to a quota
    tree generated from an ElasticQuotaProfile, the profile's node selector is
    ANDed into the pod's scheduling constraints so the pod can only land on
    the tree's nodes.

    We merge into ``spec.nodeSelector`` (our feasibility model's affinity
    input).  A key the pod already pins to a DIFFERENT value stays — the AND
    of conflicting requirements is unsatisfiable either way, and keeping the
    pod's own term surfaces the conflict in diagnosis rather than silently
    rewriting user intent.
    """

    def __init__(self):
        self.quota_tree: dict[str, str] = {}          # quota name -> tree id
        self.tree_selector: dict[str, dict[str, str]] = {}

    def set_quota(self, quota: crds.ElasticQuota) -> None:
        if quota.tree_id:
            self.quota_tree[quota.name] = quota.tree_id

    def set_profile_selector(
        self, tree_id: str, node_selector: Mapping[str, str]
    ) -> None:
        self.tree_selector[tree_id] = dict(node_selector)

    def mutate(self, pod: dict, operation: str = "CREATE") -> bool:
        """Returns True when the pod was mutated."""
        if operation != "CREATE":
            return False
        labels = _labels(pod)
        quota = labels.get(ext.LABEL_QUOTA_NAME) or pod.get(
            "metadata", {}
        ).get("namespace", "")
        tree = self.quota_tree.get(quota)
        if tree is None:
            return False
        selector = self.tree_selector.get(tree)
        if not selector:
            return False
        spec = pod.setdefault("spec", {})
        node_selector = spec.setdefault("nodeSelector", {})
        changed = False
        for k, v in selector.items():
            if k not in node_selector:
                node_selector[k] = v
                changed = True
        return changed


class QuotaEvaluator:
    """Admission-time quota charge (webhook/quotaevaluate): check the pod's
    request against its ElasticQuota's remaining runtime up the tree."""

    def __init__(self, quotas: dict[str, crds.ElasticQuota] | None = None):
        self.quotas = dict(quotas or {})
        self.used: dict[str, dict[str, int]] = {}

    def set_quota(self, quota: crds.ElasticQuota) -> None:
        self.quotas[quota.name] = quota

    def _chain(self, name: str) -> list[crds.ElasticQuota]:
        chain = []
        while name and name != "root":
            quota = self.quotas.get(name)
            if quota is None:
                break
            chain.append(quota)
            name = quota.parent
        return chain

    def admit(self, quota_name: str, request: Mapping[str, int]) -> Optional[str]:
        """None = admitted (and charged); otherwise the rejection reason."""
        chain = self._chain(quota_name)
        if not chain:
            return None  # no quota -> no constraint (reference default-allow)
        for quota in chain:
            used = self.used.get(quota.name, {})
            for resource, amount in request.items():
                cap = quota.max.get(resource)
                if cap is None:
                    continue
                if used.get(resource, 0) + amount > cap:
                    return (
                        f"exceeded quota {quota.name}: {resource} "
                        f"{used.get(resource, 0)}+{amount} > {cap}"
                    )
        for quota in chain:
            used = self.used.setdefault(quota.name, {})
            for resource, amount in request.items():
                used[resource] = used.get(resource, 0) + amount
        return None

    def release(self, quota_name: str, request: Mapping[str, int]) -> None:
        for quota in self._chain(quota_name):
            used = self.used.get(quota.name, {})
            for resource, amount in request.items():
                used[resource] = max(0, used.get(resource, 0) - amount)
