"""Env-knob + operator-manifest registry and its cross-checks.

``KNOWN_ENV`` is the curated source of truth for every
``DYNAMO_TPU_*`` / ``FRONTEND_*`` / ``DRAIN_*`` environment knob the
stack reads; ``MANIFEST_KEYS`` maps every `TpuGraphDeployment` service
manifest key the operator consumes (operator/materialize.py) to the env
vars it materializes. The ``env-registry`` rule keeps all three planes
honest:

- an env read in code that is missing from ``KNOWN_ENV`` is an
  *undocumented knob*;
- a ``KNOWN_ENV`` entry no module reads any more is a *stale registry
  entry*;
- an env name the operator materializes that no module reads is a
  *dangling manifest knob* (the PR-6 class of rot: an operator field
  that silently does nothing);
- ``docs/config.md`` must carry the exact ``dump_registry()`` output
  between the ``dynalint:config-ref`` markers, so the operator-facing
  configuration reference can never drift from code
  (regenerate: ``python scripts/dynalint.py --dump-registry``).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from dynamo_tpu.analysis.core import (Checker, Finding, ImportMap, Repo,
                                      const_str, module_string_consts,
                                      qual_tail)

ENV_PREFIX_RE = re.compile(r"^(DYNAMO_TPU_|FRONTEND_|DRAIN_)[A-Z0-9_]+$")

MATERIALIZE_REL = "dynamo_tpu/operator/materialize.py"
CONFIG_DOC_BEGIN = "<!-- dynalint:config-ref:begin -->"
CONFIG_DOC_END = "<!-- dynalint:config-ref:end -->"

# --------------------------------------------------------------------------
# Curated env registry: name -> one-line operator-facing description.
# Adding an env read to the tree without a row here is a finding; so is
# leaving a row behind after the last read is deleted.
# --------------------------------------------------------------------------
KNOWN_ENV: Dict[str, str] = {
    "DRAIN_HANDOFF_GRACE_S":
        "worker drain: seconds granted to in-flight stream handoff before "
        "the hard stop",
    "DRAIN_TIMEOUT_S":
        "worker SIGTERM drain budget: admission off, in-flight handoff, "
        "KV demote (operator aligns terminationGracePeriodSeconds)",
    "DYNAMO_TPU_BATCH_BURN_ADMIT":
        "preemptible batch tier: batch-class tenants admit only while "
        "every interactive fast-window SLO burn is below this "
        "(default 1.0; 0 disables the gate)",
    "DYNAMO_TPU_BREAKER_COOLDOWN_S":
        "circuit breaker: cooldown before a tripped worker gets a "
        "half-open probe",
    "DYNAMO_TPU_BREAKER_THRESHOLD":
        "circuit breaker: consecutive failures that trip a worker out of "
        "rotation",
    "DYNAMO_TPU_BUILD_DIR":
        "native runtime: build/cache directory (default "
        "<checkout>/.dynamo_cache/native)",
    "DYNAMO_TPU_CHIP":
        "TPU chip generation override (v4/v5e/v5p/v6e) for utilization "
        "denominators in engine metrics",
    "DYNAMO_TPU_COORDINATOR":
        "multi-host: JAX coordinator address host:port",
    "DYNAMO_TPU_DEADLINE_S":
        "default per-request deadline (seconds) when the request carries "
        "none",
    "DYNAMO_TPU_DEFAULT_IMAGE":
        "operator: image for services that do not pin one in "
        "extraPodSpec.mainContainer",
    "DYNAMO_TPU_FAULTS":
        "fault injection spec for robustness drills (site=prob[,...])",
    "DYNAMO_TPU_FAULT_SEED":
        "fault injection RNG seed (deterministic drills)",
    "DYNAMO_TPU_FLIGHT_RECORDS":
        "flight-recorder ring depth; 0 disables, unset = 512",
    "DYNAMO_TPU_FRONTEND_ID":
        "stable frontend replica identity (journal-record origin + gossip "
        "subjects); operator sets it from pod metadata.name",
    "DYNAMO_TPU_GANG_DOMAIN":
        "multi-host gang: headless-service domain the followers resolve "
        "the coordinator through",
    "DYNAMO_TPU_GANG_SIZE":
        "multi-host gang: hosts per replica (from the hostsPerReplica "
        "manifest key)",
    "DYNAMO_TPU_INTEGRITY":
        "watchdog integrity sentinels: `off`, `logits` (default: finite "
        "checks riding the existing readbacks) or `full` (adds KV-page "
        "checksums at the KVBM demote/onboard boundary)",
    "DYNAMO_TPU_KVBM_DISK_DIR":
        "KVBM disk tier: spill directory (unset = no disk tier)",
    "DYNAMO_TPU_KVBM_H2D_GBPS":
        "KVBM cost gate: host-to-device bandwidth override (GB/s) for the "
        "restore-vs-recompute model",
    "DYNAMO_TPU_KVBM_HOST_BLOCKS":
        "KVBM host tier capacity in KV blocks (worker CLI "
        "--kvbm-host-blocks default)",
    "DYNAMO_TPU_LORA_ADAPTERS":
        "adapters registered at boot: {name,path} maps or name=/path "
        "entries (worker CLI --lora-adapters default)",
    "DYNAMO_TPU_LORA_RANK":
        "max LoRA rank a device slot holds (worker CLI --lora-max-rank "
        "default)",
    "DYNAMO_TPU_LORA_SLOTS":
        "device-resident adapter slots (worker CLI --lora-slots default)",
    "DYNAMO_TPU_MAX_INFLIGHT":
        "frontend fleet-wide in-flight admission cap; over it requests "
        "get 429 + Retry-After (0 = off)",
    "DYNAMO_TPU_MODEL_VERSION":
        "weight version label this worker boots on (engine CLI "
        "--model-version default; operator sets it from `modelVersion` "
        "so replacement pods match the fleet's rollout target)",
    "DYNAMO_TPU_NUM_PROCESSES":
        "multi-host: total JAX process count",
    "DYNAMO_TPU_PREEMPTIBLE":
        "marks this worker's capacity reclaimable (spot pool): "
        "advertised in heartbeat stats; the reclaim drain path applies "
        "(operator sets it from `preemptible: true`)",
    "DYNAMO_TPU_PROCESS_ID":
        "multi-host: this host's process index",
    "DYNAMO_TPU_QOS_BURN_SHED":
        "per-tenant QoS: shed over-share tenants when a matching SLO's "
        "fast-window burn rate exceeds this",
    "DYNAMO_TPU_QUARANTINE_WINDOW_S":
        "watchdog: a second trip within this many seconds of the first "
        "quarantines the engine permanently (default 300)",
    "DYNAMO_TPU_RECLAIM_DEADLINE_S":
        "default hard drain deadline (seconds) for a /internal/reclaim "
        "notice that carries none (align with the spot pool's advertised "
        "reclamation grace)",
    "DYNAMO_TPU_RECOVERY":
        "stream-recovery journaling kill switch (0 disables; default on)",
    "DYNAMO_TPU_ROLLOUT_DRAIN_MODE":
        "hot weight swap: how in-flight streams cross the flip — "
        "`finish` (default: they complete on the old version, admissions "
        "hold) or `handoff` (journaled streams resume on a peer, flip "
        "immediately)",
    "DYNAMO_TPU_ROLLOUT_HEADROOM_BYTES":
        "hot weight swap: override the device-reported free-HBM figure "
        "the stage budget check uses (also how backends that report no "
        "memory stats get a budget)",
    "DYNAMO_TPU_ROLLOUT_HEADROOM_MARGIN":
        "hot weight swap: fractional slack demanded on top of the "
        "incoming tree's bytes before staging proceeds (default 0.05)",
    "DYNAMO_TPU_ROLLOUT_MAX_BURN":
        "rollout controller: fast-window SLO burn above this mid-rollout "
        "rolls every flipped pod back to the previous version "
        "(default 1.0)",
    "DYNAMO_TPU_ROLLOUT_STEP_S":
        "rollout controller: seconds between per-pod flips — paced so "
        "the burn window can react to a bad canary (default 15)",
    "DYNAMO_TPU_SLOW_REQUEST_S":
        "tracing: request duration that pins its span to /debug/spans as "
        "slow (default 10s)",
    "DYNAMO_TPU_SLO_ERROR_RATE":
        "scalar SLO shorthand: error-rate budget for one wildcard target",
    "DYNAMO_TPU_SLO_GOAL":
        "scalar SLO shorthand: attainment goal for the latency "
        "objectives (default 0.99)",
    "DYNAMO_TPU_SLO_ITL_MS":
        "scalar SLO shorthand: inter-token-latency target (ms)",
    "DYNAMO_TPU_SLO_TARGETS":
        "JSON list of per-model/role/tenant SLO target specs "
        "(observability/slo.py target_from_dict)",
    "DYNAMO_TPU_SLO_TTFT_MS":
        "scalar SLO shorthand: time-to-first-token target (ms)",
    "DYNAMO_TPU_SPEC_ADAPTIVE_K":
        "speculation v3: enable the per-slot adaptive window controller "
        "(shrink on zero-accept windows, grow on full-accept streaks)",
    "DYNAMO_TPU_SPEC_DRAFTER":
        "speculation v3: proposer selection — ngram (prompt lookup) | "
        "model (the draft model below)",
    "DYNAMO_TPU_SPEC_DRAFT_MODEL":
        "speculation v3: small same-tokenizer draft model name for the "
        "model drafter",
    "DYNAMO_TPU_SPEC_DRAFT_MODEL_PATH":
        "speculation v3: local checkpoint dir for the draft model",
    "DYNAMO_TPU_SPEC_DRAFT_PAGES":
        "speculation v3: draft KV pool size in pages (0 = auto: "
        "max(K+2, num_pages/8); engine init enforces >= K+1)",
    "DYNAMO_TPU_SP_STRATEGY":
        "sequence-parallel strategy override for long-context prefill",
    "DYNAMO_TPU_STEP_DEADLINE_S":
        "watchdog: hard per-seam device dispatch/readback deadline "
        "(seconds); unset = warmup-measured EWMA x margin with a floor",
    "DYNAMO_TPU_TENANTS":
        "JSON tenant-class list (weights, priorities, caps, API keys) — "
        "frontend admission and engine QoS read the same classes",
    "DYNAMO_TPU_TIMELINE":
        "step-timeline kill switch (0/false/off/no disables; default on)",
    "DYNAMO_TPU_TIMELINE_RECORDS":
        "step-timeline exact-interval ring depth (default 256; 0 keeps "
        "the streaming phase digests but drops the ring)",
    "DYNAMO_TPU_TRACE":
        "tracing kill switch (0/false/off/no disables; checked per call)",
    "DYNAMO_TPU_TRACE_BUFFER":
        "tracing: process-global span ring depth (default 2048)",
    "DYNAMO_TPU_TRANSFER_BIND":
        "KV transfer plane bind address override",
    "FRONTEND_DRAIN_S":
        "frontend SIGTERM drain budget: healthz flips 503, in-flight "
        "streams get this long before the hard stop",
    "FRONTEND_URL":
        "worker: frontend base URL for registration + heartbeats "
        "(operator points it at the frontend Service)",
}

# --------------------------------------------------------------------------
# Operator manifest keys (TpuGraphDeployment service spec) -> the env vars
# materialize.py derives from them ('' envs = structural key, no env).
# --------------------------------------------------------------------------
MANIFEST_KEYS: Dict[str, Tuple[Tuple[str, ...], str]] = {
    "componentType": ((), "frontend / worker / planner — selects the "
                          "materializer and pod shape"),
    "subComponentType": ((), "worker refinement (prefill / decode) for "
                             "disagg routing labels"),
    "replicas": ((), "pod replica count (gang: replicas × "
                     "hostsPerReplica pods)"),
    "resources": ((), "container resources (TPU chips under limits)"),
    "extraPodSpec": ((), "pod-spec overlay; mainContainer pins the "
                         "image/command"),
    "envs": ((), "verbatim extra container env list"),
    "envFromSecret": ((), "envFrom secretRef for API keys etc."),
    "volumeMounts": ((), "extra container volume mounts"),
    "pvcs": ((), "PersistentVolumeClaims to create/attach"),
    "configMapVolumes": ((), "ConfigMap-backed volumes"),
    "tpuAccelerator": ((), "GKE TPU accelerator nodeSelector value"),
    "tpuTopology": ((), "GKE TPU topology nodeSelector value"),
    "hostsPerReplica": (("DYNAMO_TPU_GANG_SIZE", "DYNAMO_TPU_GANG_DOMAIN"),
                        "multi-host gang width; materializes the gang "
                        "size + coordinator discovery domain"),
    "drainSeconds": (("DRAIN_TIMEOUT_S", "FRONTEND_DRAIN_S"),
                     "graceful-drain budget (also sets the pod's "
                     "terminationGracePeriodSeconds)"),
    "flightRecords": (("DYNAMO_TPU_FLIGHT_RECORDS",),
                      "flight-recorder ring depth per pod"),
    "kvbmHostBlocks": (("DYNAMO_TPU_KVBM_HOST_BLOCKS",),
                       "KVBM host tier capacity (pair with a "
                       "resources.limits.memory bump)"),
    "kvbmDiskDir": (("DYNAMO_TPU_KVBM_DISK_DIR",),
                    "KVBM disk tier directory (usually a PVC mount)"),
    "loraAdapters": (("DYNAMO_TPU_LORA_ADAPTERS",),
                     "adapters the worker registers at boot"),
    "loraSlots": (("DYNAMO_TPU_LORA_SLOTS",),
                  "device-resident adapter slots"),
    "loraMaxRank": (("DYNAMO_TPU_LORA_RANK",),
                    "max adapter rank the slots are sized for"),
    "sloTargets": (("DYNAMO_TPU_SLO_TTFT_MS", "DYNAMO_TPU_SLO_ITL_MS",
                    "DYNAMO_TPU_SLO_ERROR_RATE", "DYNAMO_TPU_SLO_GOAL",
                    "DYNAMO_TPU_SLO_TARGETS"),
                   "declarative SLOs: scalar map -> the four shorthand "
                   "envs; list of specs -> the JSON env"),
    "tenants": (("DYNAMO_TPU_TENANTS",),
                "tenant QoS classes, identical on frontend and workers"),
    "drafter": (("DYNAMO_TPU_SPEC_DRAFTER",),
                "speculative proposer the worker boots with: ngram | "
                "model"),
    "draftModel": (("DYNAMO_TPU_SPEC_DRAFT_MODEL",
                    "DYNAMO_TPU_SPEC_DRAFT_MODEL_PATH",
                    "DYNAMO_TPU_SPEC_DRAFT_PAGES"),
                   "draft model for the model drafter: a name string, or "
                   "{model, path, pages} to also pin the checkpoint dir "
                   "and draft KV pool size"),
    "modelVersion": (("DYNAMO_TPU_MODEL_VERSION",),
                     "target weight version: fresh pods boot on it; the "
                     "controller's rollout_tick flips the running fleet "
                     "in place (burn-gated, one pod per step)"),
    "preemptible": (("DYNAMO_TPU_PREEMPTIBLE",),
                    "spot/reclaimable worker pool: GKE spot nodeSelector "
                    "+ toleration, reclaim drain semantics"),
    "reclaimDeadlineSeconds": (("DYNAMO_TPU_RECLAIM_DEADLINE_S",),
                               "default hard deadline for reclamation "
                               "notices on this pool"),
}

# Envs the operator materializes that no *manifest key* owns (fieldRefs,
# operator-computed values); they still must be read somewhere.
OPERATOR_INTERNAL_ENVS: Set[str] = {
    "DYNAMO_TPU_DEFAULT_IMAGE",   # operator's own image fallback knob
    "DYNAMO_TPU_FRONTEND_ID",     # fieldRef: pod metadata.name
    "FRONTEND_URL",               # computed from the frontend Service name
}


@dataclass
class EnvRead:
    name: str
    path: str
    line: int


def _environ_like(imap: ImportMap, node: ast.AST) -> bool:
    """os.environ in any spelling, plus the injectable-mapping idiom: a
    local named ``env`` holding an environ Mapping (slo.targets_from_env
    takes ``env=os.environ`` for tests — its reads are still env reads)."""
    if imap.resolve(node) in ("os.environ", "environ"):
        return True
    return isinstance(node, ast.Name) and node.id == "env"


def collect_env_reads(repo: Repo) -> List[EnvRead]:
    """Every env access through os.environ / os.getenv (get, [],
    setdefault, pop), with module-level string-constant indirection
    resolved (the CAPACITY_ENV pattern in observability/flight.py)."""
    reads: List[EnvRead] = []
    for src in repo.files:
        if src.tree is None:
            continue
        imap = ImportMap(src.tree)
        consts = module_string_consts(src.tree)

        def note(name_node: ast.AST, line: int) -> None:
            name = const_str(name_node, consts)
            if name and ENV_PREFIX_RE.match(name):
                reads.append(EnvRead(name, src.rel, line))

        for node in ast.walk(src.tree):
            if isinstance(node, ast.Subscript):
                if _environ_like(imap, node.value):
                    note(node.slice, node.lineno)
            elif isinstance(node, ast.Call):
                origin = imap.resolve(node.func)
                if origin in ("os.getenv", "getenv") and node.args:
                    note(node.args[0], node.lineno)
                elif qual_tail(node.func) in ("get", "setdefault", "pop") \
                        and isinstance(node.func, ast.Attribute) \
                        and _environ_like(imap, node.func.value) \
                        and node.args:
                    note(node.args[0], node.lineno)
    return reads


def collect_materialized_envs(src) -> List[Tuple[str, int]]:
    """Env-name string constants in operator/materialize.py — the set of
    knobs the operator can set on pods."""
    if src is None or src.tree is None:
        return []
    out: List[Tuple[str, int]] = []
    seen: Set[str] = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and ENV_PREFIX_RE.match(node.value) \
                and node.value not in seen:
            seen.add(node.value)
            out.append((node.value, node.lineno))
    return sorted(out)


def dump_registry(repo: Repo,
                  known_env: Optional[Dict[str, str]] = None,
                  manifest_keys: Optional[Dict[str, Tuple[Tuple[str, ...],
                                                          str]]] = None
                  ) -> str:
    """The generated configuration reference (docs/config.md body).
    Deterministic: sorted tables, repo-relative read-site module lists."""
    known_env = KNOWN_ENV if known_env is None else known_env
    manifest_keys = MANIFEST_KEYS if manifest_keys is None else manifest_keys
    reads = collect_env_reads(repo)
    readers: Dict[str, Set[str]] = {}
    for r in reads:
        readers.setdefault(r.name, set()).add(r.path)
    lines = [
        CONFIG_DOC_BEGIN,
        "",
        "### Environment knobs",
        "",
        "| Env var | Read by | Purpose |",
        "|---|---|---|",
    ]
    for name in sorted(known_env):
        mods = ", ".join(f"`{m}`" for m in sorted(readers.get(name, ())))
        lines.append(f"| `{name}` | {mods or '—'} | {known_env[name]} |")
    lines += [
        "",
        "### Operator manifest keys",
        "",
        "| Manifest key | Materializes | Purpose |",
        "|---|---|---|",
    ]
    for key in sorted(manifest_keys):
        envs, desc = manifest_keys[key]
        env_cell = ", ".join(f"`{e}`" for e in envs) or "—"
        lines.append(f"| `{key}` | {env_cell} | {desc} |")
    lines += ["", CONFIG_DOC_END]
    return "\n".join(lines)


class EnvRegistryChecker(Checker):
    name = "env-registry"

    def __init__(self,
                 known_env: Optional[Dict[str, str]] = None,
                 manifest_keys: Optional[Dict[str, Tuple[Tuple[str, ...],
                                                         str]]] = None,
                 operator_internal: Optional[Set[str]] = None):
        self.known_env = KNOWN_ENV if known_env is None else known_env
        self.manifest_keys = (MANIFEST_KEYS if manifest_keys is None
                              else manifest_keys)
        self.operator_internal = (OPERATOR_INTERNAL_ENVS
                                  if operator_internal is None
                                  else operator_internal)

    def run(self, repo: Repo) -> Iterable[Finding]:
        reads = collect_env_reads(repo)
        read_names = {r.name for r in reads}

        # 1. undocumented knob: read in code, missing from the registry
        seen: Set[Tuple[str, str]] = set()
        for r in reads:
            if r.name in self.known_env:
                continue
            if (r.name, r.path) in seen:  # one finding per (env, file)
                continue
            seen.add((r.name, r.path))
            yield Finding(
                rule=self.name, path=r.path, line=r.line,
                message=(f"env {r.name} is read here but has no "
                         f"KNOWN_ENV registry row "
                         f"(dynamo_tpu/analysis/registry.py)"),
                key=f"undocumented:{r.name}",
            )

        mat = repo.file(MATERIALIZE_REL)
        if mat is None:
            return  # fixture run without the operator tree: local rule only
        mat_envs = collect_materialized_envs(mat)
        mat_names = {n for n, _ in mat_envs}

        # 2. stale registry entry: documented, read nowhere
        for name in sorted(self.known_env):
            if name not in read_names:
                yield Finding(
                    rule=self.name, path="dynamo_tpu/analysis/registry.py",
                    line=1,
                    message=(f"KNOWN_ENV entry {name} is read by no "
                             f"scanned module (stale registry row)"),
                    key=f"stale-registry:{name}",
                )

        # 3. dangling manifest knob: operator sets it, nobody reads it
        for name, line in mat_envs:
            if name not in read_names:
                yield Finding(
                    rule=self.name, path=mat.rel, line=line,
                    message=(f"operator materializes env {name} but no "
                             f"scanned module reads it (dangling knob)"),
                    key=f"dangling:{name}",
                )

        # 4. manifest mapping consistency
        mapped: Set[str] = set()
        for key in sorted(self.manifest_keys):
            envs, _ = self.manifest_keys[key]
            mapped.update(envs)
            if f'"{key}"' not in mat.text and f"'{key}'" not in mat.text:
                yield Finding(
                    rule=self.name, path=mat.rel, line=1,
                    message=(f"MANIFEST_KEYS entry {key!r} no longer "
                             f"appears in operator/materialize.py "
                             f"(stale manifest key)"),
                    key=f"stale-manifest-key:{key}",
                )
            for env in envs:
                if env not in mat_names:
                    yield Finding(
                        rule=self.name, path=mat.rel, line=1,
                        message=(f"manifest key {key!r} maps to env {env} "
                                 f"which materialize.py never sets"),
                        key=f"unmapped-env:{key}:{env}",
                    )
        for name, line in mat_envs:
            if name not in mapped and name not in self.operator_internal \
                    and name in read_names:
                yield Finding(
                    rule=self.name, path=mat.rel, line=line,
                    message=(f"materialized env {name} is owned by no "
                             f"MANIFEST_KEYS entry (add the mapping or "
                             f"list it in OPERATOR_INTERNAL_ENVS)"),
                    key=f"unowned-env:{name}",
                )

        # 5. docs/config.md generated block must match dump_registry()
        if repo.config_doc is not None:
            want = dump_registry(repo, self.known_env, self.manifest_keys)
            got = _extract_block(repo.config_doc)
            if got is None:
                yield Finding(
                    rule=self.name, path="docs/config.md", line=1,
                    message=("docs/config.md has no dynalint:config-ref "
                             "block — regenerate with "
                             "`python scripts/dynalint.py --dump-registry`"),
                    key="config-doc:missing",
                )
            elif got.strip() != want.strip():
                yield Finding(
                    rule=self.name, path="docs/config.md", line=1,
                    message=("docs/config.md config-ref block is stale — "
                             "regenerate with "
                             "`python scripts/dynalint.py --dump-registry`"),
                    key="config-doc:stale",
                )
        elif repo.observability_doc is not None:
            # real-tree run (docs present) but no config.md at all
            yield Finding(
                rule=self.name, path="docs/config.md", line=1,
                message=("docs/config.md is missing — generate it with "
                         "`python scripts/dynalint.py --dump-registry`"),
                key="config-doc:absent",
            )


def _extract_block(doc: str) -> Optional[str]:
    try:
        i = doc.index(CONFIG_DOC_BEGIN)
        j = doc.index(CONFIG_DOC_END)
    except ValueError:
        return None
    return doc[i:j + len(CONFIG_DOC_END)]
