"""Task launcher — the per-step executor inside the worker process.

The KFP v2 launcher analog (⟨pipelines: backend/src/v2/component — launcher⟩,
SURVEY.md §2.4/§3.5): the C++ pipeline controller resolves a task's inputs
and writes a task-spec JSON; this process materializes output directories,
runs the user step (packaged python function or raw command with
placeholders), and exits 0 only if every declared output was produced.
Artifact upload/download collapses to filesystem paths (local artifact
store); lineage recording stays in the controller, which digests the
outputs on success.

Task spec:
    {"component": {...component IR...},
     "params":  {"n": 100},                  # fully resolved values
     "inputs":  {"data": "/.../artifacts/preprocess/out"},
     "outputs": {"model": "/.../artifacts/train/model"}}
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


class LauncherError(RuntimeError):
    pass


def _resolve_placeholders(text: str, params: dict, inputs: dict,
                          outputs: dict) -> str:
    for key, val in params.items():
        if isinstance(val, (list, dict)):
            val = json.dumps(val)
        text = text.replace("{{params.%s}}" % key, str(val))
    for key, val in inputs.items():
        text = text.replace("{{inputs.%s}}" % key, val)
    for key, val in outputs.items():
        text = text.replace("{{outputs.%s}}" % key, val)
    return text


RESULT_OUTPUT = "__result__"  # implicit artifact carrying the return value


def _stage_collected(name: str, paths: list) -> str:
    """Materialize a fan-in input: a directory of numbered symlinks to the
    per-iteration artifacts, handed to the component as one path."""
    import tempfile

    stage = tempfile.mkdtemp(prefix=f"tpk-collect-{name}-")
    for i, p in enumerate(paths):
        if not os.path.exists(p):
            raise LauncherError(
                f"collected input {name!r}[{i}] missing at {p}")
        # Zero-padded so lexicographic listing preserves iteration order
        # past 10 items.
        os.symlink(os.path.abspath(p), os.path.join(stage, f"{i:05d}"))
    return stage


def run_task(spec: dict) -> None:
    comp = spec["component"]
    params = dict(comp.get("defaults") or {})
    params.update(spec.get("params") or {})
    inputs = spec.get("inputs") or {}
    outputs = spec.get("outputs") or {}

    for name, path in list(inputs.items()):
        if isinstance(path, list):  # Collected fan-in over loop iterations
            inputs[name] = _stage_collected(name, path)
        elif not os.path.exists(path):
            raise LauncherError(f"input artifact {name!r} missing at {path}")
    for path in outputs.values():
        os.makedirs(path, exist_ok=True)
    result_dir = outputs.pop(RESULT_OUTPUT, None)

    kind = comp.get("kind", "python")
    if kind == "python":
        # Re-hydrate the Component by exec'ing its captured source with the
        # DSL names in scope, then call the underlying function with params
        # + artifact paths (the KFP "lightweight python component" flow).
        from kubeflow_tpu.pipelines import dsl

        scope = {"component": dsl.component,
                 "container_component": dsl.container_component,
                 "InputArtifact": dsl.InputArtifact,
                 "OutputArtifact": dsl.OutputArtifact}
        # dont_inherit: exec must not leak this module's `from __future__
        # import annotations` into the component (it would stringify the
        # signature annotations the DSL dispatches on).
        code = compile(comp["source"], f"<component {comp['name']}>",
                       "exec", dont_inherit=True)
        exec(code, scope)  # noqa: S102 — the source IS the step
        obj = scope.get(comp["name"])
        if isinstance(obj, dsl.Component):
            fn = obj.fn
        elif callable(obj):
            fn = obj
        else:
            raise LauncherError(
                f"component source did not define {comp['name']!r}")
        ret = fn(**params, **inputs, **outputs)
        if (comp.get("returns") and result_dir
                and int(os.environ.get("TPK_PROC_ID", "0")) == 0):
            # The return value is the task's output parameter — recorded
            # as a tiny artifact the controller reads back for
            # dsl.Condition / Collected consumers. Process 0 only: in a
            # multi-replica gang every process runs this code against the
            # same shared path, and concurrent writes could interleave
            # into invalid JSON.
            with open(os.path.join(result_dir, "value.json"), "w") as fh:
                json.dump(ret, fh)
    elif kind == "command":
        argv = [_resolve_placeholders(a, params, inputs, outputs)
                for a in comp.get("argv") or []]
        if not argv:
            raise LauncherError("command component has empty argv")
        env = dict(os.environ)
        cpu = env.get("TPK_CPU_DEVICES")
        if cpu:
            # jax config can't cross the process boundary; give the child
            # the env form of CPU test mode instead.
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"{env.get('XLA_FLAGS', '')} "
                f"--xla_force_host_platform_device_count={cpu}").strip()
        rc = subprocess.call(argv, env=env)
        if rc != 0:
            raise LauncherError(f"command exited {rc}: {argv}")
    else:
        raise LauncherError(f"unknown component kind {kind!r}")

    missing = [n for n, p in outputs.items()
               if not os.path.exists(p) or not os.listdir(p)]
    if missing:
        raise LauncherError(
            f"component {comp.get('name')!r} did not populate declared "
            f"outputs: {missing}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tpk-launcher")
    ap.add_argument("--spec", required=True, help="task spec JSON path")
    args = ap.parse_args(argv)
    with open(args.spec) as fh:
        spec = json.load(fh)
    # The gang launcher signals CPU test mode via env (the argv form
    # belongs to the trainer entrypoint). Configure before a python
    # component body touches a jax backend; command components get the
    # env form injected at exec instead (no jax import paid here).
    cpu = os.environ.get("TPK_CPU_DEVICES")
    if cpu and spec.get("component", {}).get("kind", "python") == "python":
        from kubeflow_tpu.utils.devices import force_cpu_device_count

        force_cpu_device_count(int(cpu))
    try:
        run_task(spec)
    except Exception as e:
        print(f"launcher: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
