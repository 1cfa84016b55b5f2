"""P7: config-surface drift.

One deploy config (``DeployConfig``) fans out into env vars the
manifests inject, ``TPUSERVE_*`` overrides ``load_config`` reads
dynamically, server/gateway/autoscaler argparse flags, and the README
flag tables operators actually read.  Each hop is a hand-written string
— so a var the engine reads but nothing sets, a DeployConfig field no
manifest consumes, or a README row naming a flag that no longer exists
are all one rename away.  Checks, in the P5 both-directions style:

- ``env-var-unreachable``: a ``TPUSERVE_*`` var read inside
  ``tpuserve/`` that no DeployConfig field override reaches, no
  manifest injects, and that is not declared debug-only/operator-set —
  a knob the deploy layer cannot turn.
- ``env-var-undocumented``: a read var absent from README (debug-only
  vars are exempt; their config reason string is the documentation).
- ``env-var-doc-drift``: a ``TPUSERVE_*`` named in a README table row
  that nothing reads, no DeployConfig field backs, and no manifest
  emits (renamed or removed).
- ``env-shell-stale``: an ``env_shell`` registry entry whose var no
  longer appears in the named shell script.
- ``deploy-field-unused``: a DeployConfig field no provision module
  outside config.py ever reads — config that cannot land in any
  manifest env/flag.
- ``flag-undocumented``: a server/gateway/autoscaler argparse flag
  absent from README.
- ``flag-doc-drift``: a ``--flag`` in a README table row that no
  in-repo argparse surface defines.

Suppress with ``# tpulint: config-ok(reason)``.
"""

from __future__ import annotations

import os
import re

from tools.tpulint.core import Config, Finding
from tools.tpulint.interface import (argparse_flags, attr_reads,
                                     deploy_config_fields, env_reads,
                                     expand_paths, get_source,
                                     manifest_env_names)

NAME = "config-surface"
TAG = "config-ok"

RULES = {
    "env-var-unreachable": "a TPUSERVE_* read in tpuserve/ that no "
                           "DeployConfig field, manifest env, or "
                           "debug-only/operator registry reaches",
    "env-var-undocumented": "a TPUSERVE_* read site absent from the "
                            "README (debug-only vars exempt)",
    "env-var-doc-drift": "a README table row names a TPUSERVE_* var "
                         "nothing reads/backs/emits",
    "env-shell-stale": "an env_shell registry entry whose var vanished "
                       "from the named shell script",
    "deploy-field-unused": "a DeployConfig field no provision module "
                           "consumes — it can't land in any manifest",
    "flag-undocumented": "a server/gateway/autoscaler CLI flag absent "
                         "from the README flag tables",
    "flag-doc-drift": "a README table row names a --flag no argparse "
                      "surface defines",
}

_FLAG_RE = re.compile(r"--[a-z0-9][a-z0-9-]*")


def _backtick_text(readme: str) -> str:
    return " ".join(re.findall(r"`([^`]*)`", readme))


def _table_lines(readme: str):
    for i, line in enumerate(readme.splitlines(), start=1):
        if line.lstrip().startswith("|"):
            yield i, line


def run(files: dict, config: Config, repo_root: str) -> list:
    findings: list = []
    sec = config.section("config_surface")
    prefix = sec.get("env_prefix", "TPUSERVE_")
    env_re = re.compile(re.escape(prefix) + r"[A-Z0-9_]+")

    srcs = dict(files)
    # argparse surfaces join the scan set explicitly so a subset lint
    # (``tpulint tpuserve/runtime``) still knows the full flag universe
    # when judging README table rows
    wanted = list(expand_paths(repo_root, sec.get("extra_paths", ()))) \
        + list(sec.get("argparse_files", ()))
    for rel in wanted:
        if rel not in srcs:
            got = get_source(files, repo_root, rel, errors=findings)
            if got is not None:
                srcs[rel] = got

    # ---- the model ---------------------------------------------------
    reads: dict = {}            # var -> first Site anywhere (doc rule)
    # var -> first Site under tpuserve/ — the reachability rule judges
    # engine-side reads specifically; keying off the first site found
    # anywhere would let a tools/ read (sorted earlier) mask an
    # unreachable engine read of the same var
    tpu_reads: dict = {}
    flags_all: set = set()      # every argparse flag in scanned sources
    for rel in sorted(srcs):
        _src, tree = srcs[rel]
        for s in env_reads(rel, tree, prefix):
            reads.setdefault(s.name, s)
            if s.file.startswith("tpuserve/"):
                tpu_reads.setdefault(s.name, s)
        for s in argparse_flags(rel, tree):
            flags_all.add(s.name)

    dc = get_source(srcs, repo_root, sec.get("deploy_config", ""))
    fields = deploy_config_fields(dc[1]) if dc else {}
    overrides = {prefix + f.upper() for f in fields}

    man_rel = sec.get("manifests", "")
    man = get_source(srcs, repo_root, man_rel)
    emitted = {s.name for s in manifest_env_names(man[1], prefix)} \
        if man else set()

    debug_only = dict(sec.get("env_debug_only", {}))
    operator = set(sec.get("env_operator", ()))
    shell = dict(sec.get("env_shell", {}))

    readme_rel = sec.get("readme", "README.md")
    readme_path = os.path.join(repo_root, readme_rel)
    readme = ""
    if os.path.exists(readme_path):
        with open(readme_path, "r", encoding="utf-8") as f:
            readme = f.read()
    # documentation credit = backticked mentions anywhere PLUS raw
    # table-row text — the drift direction scans raw table lines, so an
    # unbackticked row must count as documentation for the undocumented
    # direction too (asymmetry would flag a var the README visibly has)
    table_text = " ".join(line for _ln, line in _table_lines(readme))
    doc_env = set(env_re.findall(_backtick_text(readme))) \
        | set(env_re.findall(table_text))
    doc_flags = set(_FLAG_RE.findall(_backtick_text(readme))) \
        | set(_FLAG_RE.findall(table_text))

    # ---- env vars: read sites ---------------------------------------
    for var in sorted(reads):
        site = reads[var]
        if var in tpu_reads \
                and var not in overrides and var not in emitted \
                and var not in debug_only and var not in operator:
            findings.append(Finding(
                file=tpu_reads[var].file, line=tpu_reads[var].line,
                rule="env-var-unreachable",
                message=f"env var '{var}' is read here but no "
                        "DeployConfig field override reaches it, no "
                        "manifest injects it, and it is not registered "
                        "debug-only/operator-set — the deploy layer "
                        "cannot turn this knob ([tool.tpulint."
                        "config_surface])", pass_name=NAME))
        if readme and var not in doc_env and var not in debug_only:
            findings.append(Finding(
                file=site.file, line=site.line,
                rule="env-var-undocumented",
                message=f"env var '{var}' is read here but never "
                        f"documented in {readme_rel} — add a flag-table "
                        "row/mention, or register it debug-only with a "
                        "reason", pass_name=NAME))

    # ---- env vars: README table rows --------------------------------
    if readme:
        known = (set(reads) | overrides | emitted | set(shell)
                 | operator | set(debug_only))
        reported: set = set()
        for lineno, line in _table_lines(readme):
            for var in env_re.findall(line):
                if var in known or var in reported:
                    continue
                reported.add(var)
                findings.append(Finding(
                    file=readme_rel, line=lineno,
                    rule="env-var-doc-drift",
                    message=f"README table documents env var '{var}' "
                            "which nothing reads, no DeployConfig "
                            "field backs, and no manifest emits "
                            "(renamed or removed?)", pass_name=NAME))

    # ---- shell registry staleness -----------------------------------
    for var, script in sorted(shell.items()):
        path = os.path.join(repo_root, script)
        text = ""
        if os.path.exists(path):
            with open(path, "r", encoding="utf-8") as f:
                text = f.read()
        if var not in text:
            findings.append(Finding(
                file=script, line=1, rule="env-shell-stale",
                message=f"[tool.tpulint.config_surface] env_shell "
                        f"registers '{var}' as read by {script}, but "
                        "the script no longer mentions it — drop the "
                        "registry entry or restore the read",
                pass_name=NAME))

    # ---- DeployConfig fields must land somewhere --------------------
    if dc and fields:
        prov_dir = sec.get("provision_dir", "tpuserve/provision")
        dc_rel = sec.get("deploy_config", "")
        used: set = set()
        for rel in expand_paths(repo_root, [prov_dir]):
            if rel == dc_rel:
                continue
            got = srcs.get(rel) or get_source(files, repo_root, rel)
            if got is not None:
                used |= attr_reads(got[1])
        allow = set(sec.get("deploy_field_allow", ()))
        if used:      # no provision modules at all = fixture run
            for field in sorted(set(fields) - used - allow):
                findings.append(Finding(
                    file=dc_rel, line=fields[field],
                    rule="deploy-field-unused",
                    message=f"DeployConfig.{field} is declared but no "
                            "provision module reads it — the field can "
                            "never land in a manifest env/flag (dead "
                            "deploy surface)", pass_name=NAME))

    # ---- CLI flags, both directions ---------------------------------
    if readme:
        for rel in sec.get("argparse_files", ()):
            got = srcs.get(rel) or get_source(files, repo_root, rel)
            if got is None:
                continue
            seen: set = set()
            for s in argparse_flags(rel, got[1]):
                if s.name in doc_flags or s.name in seen:
                    continue
                seen.add(s.name)
                findings.append(Finding(
                    file=rel, line=s.line, rule="flag-undocumented",
                    message=f"CLI flag '{s.name}' is not documented in "
                            f"{readme_rel} — every operator-facing "
                            "server/gateway/autoscaler flag needs a "
                            "flag-table row", pass_name=NAME))
        reported = set()
        for lineno, line in _table_lines(readme):
            for flag in _FLAG_RE.findall(" ".join(
                    re.findall(r"`([^`]*)`", line))):
                if flag in flags_all or flag in reported:
                    continue
                reported.add(flag)
                findings.append(Finding(
                    file=readme_rel, line=lineno, rule="flag-doc-drift",
                    message=f"README table documents CLI flag '{flag}' "
                            "which no argparse surface defines (renamed "
                            "or removed?)", pass_name=NAME))
    return findings
