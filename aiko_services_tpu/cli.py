# Command-line entry points.
#
# Capability parity with the reference console scripts
# (reference: pyproject.toml:36-40 — aiko, aiko_dashboard, aiko_pipeline,
# aiko_registrar; CLI autogen: aiko_services/cli.py:96-206, pipeline CLI:
# pipeline.py:874-936).
#
#   aiko_tpu registrar                  — run a registrar process
#   aiko_tpu pipeline create DEF.json   — run a pipeline from a definition
#   aiko_tpu pipeline show DEF.json     — validate + print a definition
#   aiko_tpu dashboard                  — curses service dashboard
#   aiko_tpu storage                    — run a storage service
#   aiko_tpu recorder                   — run a log recorder
#
# Transport selection: --transport memory|mqtt (AIKO_TPU_TRANSPORT env);
# mqtt interops with a real broker, memory is single-process.

from __future__ import annotations

import json
import os
import sys

import click

__all__ = ["main"]


def _make_runtime(name, transport):
    from .process import ProcessRuntime

    if transport == "mqtt":
        from .transport.mqtt import MQTT_AVAILABLE, MQTTMessage
        if not MQTT_AVAILABLE:
            raise click.ClickException(
                "mqtt transport requested but paho-mqtt is not installed")

        def factory(on_message, lwt_topic, lwt_payload, lwt_retain):
            from .utils.configuration import \
                get_transport_configuration
            config = get_transport_configuration()
            return MQTTMessage(on_message=on_message, lwt_topic=lwt_topic,
                               lwt_payload=lwt_payload,
                               lwt_retain=lwt_retain,
                               host=config.host, port=config.port,
                               username=config.username,
                               password=config.password, tls=config.tls)
        runtime = ProcessRuntime(name=name, transport_factory=factory)
    else:
        runtime = ProcessRuntime(name=name)
    return runtime.initialize()


transport_option = click.option(
    "--transport", default=lambda: os.environ.get("AIKO_TPU_TRANSPORT",
                                                  "memory"),
    type=click.Choice(["memory", "mqtt"]), help="control-plane transport")


@click.group()
def main() -> None:
    """aiko_services_tpu: TPU-native distributed service framework."""


@main.command()
@transport_option
def registrar(transport) -> None:
    """Run a registrar (primary election + service discovery)."""
    from .registrar import Registrar

    runtime = _make_runtime("registrar", transport)
    Registrar(runtime)
    click.echo(f"registrar on {runtime.topic_path} ({transport})")
    runtime.run(loop_when_no_handlers=True)


@main.group()
def pipeline() -> None:
    """Pipeline operations."""


def _snake(name: str) -> str:
    """PE_WhisperASR → pe_whisper_asr (the reference CLI's flag
    naming: aiko_services/cli.py:96-206)."""
    import re
    return re.sub(r"(?<=[a-z0-9])(?=[A-Z])|(?<=[A-Z])(?=[A-Z][a-z])",
                  "_", name).lower().replace("__", "_")


def parse_mesh_spec(spec: str | None):
    """'model=4,data=2' → a jax Mesh over the visible devices (None
    passes through: single-device ComputeRuntime).  This is the CLI
    seam that makes the parallelism modes user-reachable — the same
    axis names the elements' logical-axis rules shard over (TP
    'model', MoE 'expert', ring attention 'sequence', DP 'data')."""
    if not spec:
        return None
    axes = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise click.ClickException(
                f"--mesh: expected axis=N, got {part!r}")
        axis, _, count = part.partition("=")
        axis = axis.strip()
        if not axis:
            raise click.ClickException(
                f"--mesh: missing axis name in {part!r}")
        if axis in axes:
            raise click.ClickException(
                f"--mesh: duplicate axis {axis!r}")
        try:
            size = int(count)
        except ValueError:
            raise click.ClickException(
                f"--mesh: axis size must be an integer, got {count!r}")
        if size < 1:
            raise click.ClickException(
                f"--mesh: axis size must be >= 1, got {size}")
        axes[axis] = size
    from .parallel import create_mesh
    try:
        import math

        import jax
        # the mesh takes the first product-many devices: an axes
        # product smaller than the machine is a valid ask (e.g.
        # expert=4 on an 8-device host)
        need = math.prod(axes.values())
        return create_mesh(axes, devices=jax.devices()[:need])
    except Exception as exc:
        raise click.ClickException(
            f"--mesh {spec!r}: {exc} (visible devices may be fewer "
            f"than the axes' product; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N)")


def parse_element_flags(definition, extra_args) -> dict:
    """Autogenerated per-element parameter flags, reference-style
    (aiko_services/cli.py:96-206 turns every element parameter into a
    `--element-param` option).  Two accepted spellings per parameter:

        --PE_WhisperASR.max_tokens 24     (exact names)
        --pe-whisper-asr-max-tokens 24    (snake/kebab)

    Values parse as JSON when possible, else raw strings.  Unknown
    flags raise with the discoverable flag list (`pipeline params`)."""
    elements = [e.name for e in definition.elements]
    prefixes = {_snake(name).replace("_", "-"): name
                for name in elements}
    overrides = {}
    queue = list(extra_args)
    while queue:
        flag = queue.pop(0)
        if not flag.startswith("--"):
            raise click.ClickException(f"unexpected argument {flag!r}")
        flag = flag[2:]
        if "=" in flag:
            flag, value = flag.split("=", 1)
        elif queue:
            value = queue.pop(0)
        else:
            raise click.ClickException(f"flag --{flag} needs a value")
        key = None
        if "." in flag:
            element, param = flag.split(".", 1)
            if element in elements:
                key = f"{element}.{param}"
        else:
            kebab = flag.replace("_", "-")
            # longest prefix first: PE_Microphone must not capture
            # PE_MicrophoneSim's flags
            for prefix in sorted(prefixes, key=len, reverse=True):
                if kebab.startswith(prefix + "-"):
                    param = kebab[len(prefix) + 1:].replace("-", "_")
                    key = f"{prefixes[prefix]}.{param}"
                    break
        if key is None:
            raise click.ClickException(
                f"--{flag} matches no element of "
                f"{elements}; run `pipeline params` to list flags")
        try:
            overrides[key] = json.loads(value)
        except ValueError:
            overrides[key] = value
    return overrides


@pipeline.command(context_settings=dict(ignore_unknown_options=True))
@click.argument("definition_pathname")
@click.option("--name", default=None, help="pipeline service name")
@click.option("--stream", "stream_id", default="*",
              help="stream id to create")
@click.option("--stream-parameters", default="{}",
              help="JSON dict of stream parameters")
@click.option("--frame", "frame_json", default=None,
              help="JSON swag for one immediate frame")
@click.option("--mesh", "mesh_spec", default=None,
              help="device mesh for the ComputeRuntime, e.g. "
                   "'model=4,data=2' (TP x DP), 'expert=8' (MoE), "
                   "'sequence=8' (ring attention).  Elements shard "
                   "their params over it via their logical axes.")
@transport_option
@click.argument("element_flags", nargs=-1,
                type=click.UNPROCESSED)
def create(definition_pathname, name, stream_id, stream_parameters,
           frame_json, transport, mesh_spec, element_flags) -> None:
    """Run a pipeline from DEFINITION_PATHNAME.

    Every element parameter is additionally a flag:
    `--PE_Element.param VALUE` or `--pe-element-param VALUE`
    (see `pipeline params DEFINITION` for the list)."""
    from .compute import ComputeRuntime, enable_compile_cache
    from .pipeline import Pipeline, load_pipeline_definition

    # the one command that compiles device programs: control-plane
    # commands (registrar, dashboard, system start, ...) never import
    # jax, so a parent that spawns chip-owning children stays off the
    # chip
    enable_compile_cache()
    definition = load_pipeline_definition(definition_pathname)
    parameters = json.loads(stream_parameters)
    parameters |= parse_element_flags(definition, element_flags)
    runtime = _make_runtime(name or definition.name, transport)
    ComputeRuntime(runtime, "compute", mesh=parse_mesh_spec(mesh_spec))
    pipe = Pipeline(runtime, definition, name=name,
                    definition_pathname=definition_pathname)
    pipe.create_stream(stream_id, parameters=parameters)
    if frame_json is not None:
        pipe.post("process_frame", stream_id, json.loads(frame_json))
    click.echo(f"pipeline {pipe.name} on {pipe.topic_path} "
               f"({len(pipe.graph)} elements, {transport})")
    runtime.run(loop_when_no_handlers=True)


@pipeline.command("params")
@click.argument("definition_pathname")
def pipeline_params(definition_pathname) -> None:
    """List every element parameter as its autogenerated flags (the
    reference's discoverable-flags UX, aiko_services/cli.py:96-206)."""
    from .pipeline import load_pipeline_definition

    definition = load_pipeline_definition(definition_pathname)
    declared: dict[str, dict] = {e.name: {} for e in definition.elements}
    for key, value in (definition.parameters or {}).items():
        element, _, param = key.partition(".")
        if param and element in declared:
            declared[element][param] = value
    for element in definition.elements:
        params = declared.get(element.name, {})
        params = {**(element.parameters or {}), **params}
        click.echo(f"{element.name}:")
        if not params:
            click.echo("  (no declared parameters; any --"
                       f"{element.name}.<param> VALUE is accepted)")
        prefix = _snake(element.name).replace("_", "-")
        for param, default in sorted(params.items()):
            click.echo(f"  --{element.name}.{param} / "
                       f"--{prefix}-{param.replace('_', '-')}"
                       f"  [default: {default!r}]")


@pipeline.command()
@click.argument("definition_pathname")
@click.option("--dump", "dump_format", default=None,
              type=click.Choice(["json", "yaml"]),
              help="export the validated definition instead of "
                   "pretty-printing")
@click.option("--output", "output_pathname", default=None,
              help="write the --dump export to a file (default stdout)")
def show(definition_pathname, dump_format, output_pathname) -> None:
    """Validate and print (or --dump) a pipeline definition."""
    from .pipeline import (PipelineGraph, definition_to_dict,
                           load_pipeline_definition)

    if output_pathname and not dump_format:
        raise click.UsageError("--output requires --dump json|yaml")
    definition = load_pipeline_definition(definition_pathname)
    graph = PipelineGraph.from_definition(definition)
    graph.validate(definition)
    if dump_format:
        data = definition_to_dict(definition)
        if dump_format == "yaml":
            try:
                import yaml
            except ImportError as exc:      # pragma: no cover
                raise click.ClickException(
                    "--dump yaml needs pyyaml (pip install pyyaml); "
                    "--dump json has no extra dependency") from exc
            text = yaml.safe_dump(data, sort_keys=False)
        else:
            text = json.dumps(data, indent=2) + "\n"
        if output_pathname:
            with open(output_pathname, "w") as f:
                f.write(text)
            click.echo(f"wrote {output_pathname}")
        else:
            click.echo(text, nl=False)
        return
    click.echo(f"pipeline: {definition.name} (runtime={definition.runtime})")
    for node in graph.topological_order():
        element = definition.element(node.name)
        deploy = "remote" if element.is_remote else "local"
        click.echo(f"  {node.name}: {element.input_names} -> "
                   f"{element.output_names} [{deploy}]"
                   + (f" -> {node.successors}" if node.successors else ""))
    click.echo("valid")


@main.command()
@transport_option
def storage(transport) -> None:
    """Run a storage service (sqlite key/value)."""
    from .storage import Storage

    runtime = _make_runtime("storage", transport)
    database, _ = os.environ.get("AIKO_TPU_STORAGE", "storage.db"), None
    Storage(runtime, database_path=database)
    click.echo(f"storage ({database}) on {runtime.topic_path}")
    runtime.run(loop_when_no_handlers=True)


@main.command()
@transport_option
def recorder(transport) -> None:
    """Run a log recorder."""
    from .recorder import Recorder

    runtime = _make_runtime("recorder", transport)
    Recorder(runtime)
    click.echo(f"recorder on {runtime.topic_path}")
    runtime.run(loop_when_no_handlers=True)


@main.command()
@transport_option
def dashboard(transport) -> None:
    """Curses dashboard: live service table + EC share browser."""
    from .dashboard import run_dashboard

    runtime = _make_runtime("dashboard", transport)
    run_dashboard(runtime)


# -- system bring-up (reference: scripts/system_start.sh etc.) ---------------

_DEFAULT_STATE_FILE = "~/.aiko_tpu_system.json"


def _state_path(state_file: str):
    import pathlib
    return pathlib.Path(state_file).expanduser()


def _load_state(state_file: str) -> dict:
    import json
    path = _state_path(state_file)
    if path.exists():
        try:
            return json.loads(path.read_text())
        except (ValueError, OSError):
            return {}
    return {}


def _pid_alive(pid: int) -> bool:
    import os
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    return True


def _state_entry(value):
    """State-file values are [pid, start_time] (older files: bare
    pid → start_time None)."""
    if isinstance(value, (list, tuple)):
        return int(value[0]), value[1]
    return int(value), None


@main.group()
def system() -> None:
    """Bring a whole control plane up/down (registrar, recorder,
    storage — and mosquitto when the transport is mqtt)."""


@system.command("start")
@transport_option
@click.option("--state-file", default=_DEFAULT_STATE_FILE,
              help="where to record the spawned pids")
@click.option("--services", default="registrar,recorder,storage",
              help="comma-separated aiko_tpu subcommands to spawn")
def system_start(transport, state_file, services) -> None:
    """One-command bring-up (reference: scripts/system_start.sh —
    mosquitto + registrar + dashboard)."""
    import json
    import shutil
    import subprocess
    import sys

    from .utils.configuration import pid_start_time, pid_verified

    def _still_ours(name, value):
        pid, start = _state_entry(value)
        if not _pid_alive(pid):
            return False
        # a recycled pid (different start time) is NOT our process —
        # don't let a stale state file block startup forever; legacy
        # bare-pid entries fall back to the cmdline heuristic, which
        # must also try the service name (mirrors system_stop: a live
        # mosquitto never matches the default "aiko" marker, and
        # missing it here spawns a duplicate broker)
        if start is not None:
            return pid_verified(pid, start_time=start)
        return pid_verified(pid, name) or pid_verified(pid)

    state = {name: value
             for name, value in _load_state(state_file).items()
             if _still_ours(name, value)}
    if state:
        raise click.ClickException(
            f"system already running ({', '.join(state)}); "
            f"run `aiko_tpu system stop` first")

    if transport == "mqtt" and shutil.which("mosquitto"):
        from .utils.configuration import get_transport_configuration
        config = get_transport_configuration()
        if config.host in ("localhost", "127.0.0.1"):
            broker = subprocess.Popen(
                ["mosquitto", "-p", str(config.port)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            state["mosquitto"] = [broker.pid, pid_start_time(broker.pid)]
            click.echo(f"mosquitto: pid {broker.pid} (port {config.port})")

    for name in [s.strip() for s in services.split(",") if s.strip()]:
        child = subprocess.Popen(
            [sys.executable, "-m", "aiko_services_tpu", name,
             "--transport", transport],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        # record (pid, start_time): the exact process identity, so a
        # later `stop` can never signal a recycled pid
        state[name] = [child.pid, pid_start_time(child.pid)]
        click.echo(f"{name}: pid {child.pid}")
    _state_path(state_file).write_text(json.dumps(state))
    if transport == "memory":
        click.echo("note: memory transport is per-process — these "
                   "services are isolated; use --transport mqtt for a "
                   "multi-process system")


@system.command("stop")
@click.option("--state-file", default=_DEFAULT_STATE_FILE)
def system_stop(state_file) -> None:
    """Stop everything `system start` spawned (reference:
    scripts/system_stop.sh)."""
    import os
    import signal

    state = _load_state(state_file)
    if not state:
        click.echo("nothing recorded as running")
        return
    from .utils.configuration import pid_verified
    for name, value in state.items():
        pid, start = _state_entry(value)
        if _pid_alive(pid):
            # a stale pid file can point at a recycled pid belonging to
            # an unrelated process — only signal the exact process we
            # spawned (start-time identity when recorded; cmdline
            # heuristic for older state files)
            if start is not None:
                ok = pid_verified(pid, start_time=start)
                why = "start time changed"
            else:
                ok = pid_verified(pid, name) or pid_verified(pid)
                why = "cmdline no longer matches"
            if not ok:
                click.echo(f"{name}: pid {pid} alive but {why} — "
                           f"likely recycled, skipped")
                continue
            try:
                os.kill(pid, signal.SIGTERM)
                click.echo(f"{name}: stopped pid {pid}")
            except OSError as exc:
                click.echo(f"{name}: pid {pid} — {exc}")
        else:
            click.echo(f"{name}: pid {pid} already gone")
        try:
            # reap if the child is ours (same-process start/stop);
            # otherwise init adopts and reaps it
            os.waitpid(pid, os.WNOHANG)
        except (ChildProcessError, OSError):
            pass
    _state_path(state_file).unlink(missing_ok=True)


@system.command("status")
@click.option("--state-file", default=_DEFAULT_STATE_FILE)
def system_status(state_file) -> None:
    """Show what `system start` spawned and whether it is alive."""
    state = _load_state(state_file)
    if not state:
        click.echo("not running")
        return
    for name, value in state.items():
        pid, _ = _state_entry(value)
        click.echo(f"{name}: pid {pid} "
                   f"{'alive' if _pid_alive(pid) else 'DEAD'}")


@system.command("reset")
@transport_option
def system_reset(transport) -> None:
    """Clear durable bootstrap state — the retained registrar boot
    topic on the broker (reference: scripts/system_reset.sh)."""
    if transport == "memory":
        click.echo("memory transport keeps no retained state outside "
                   "processes; nothing to reset")
        return
    from .transport.mqtt import MQTT_AVAILABLE, MQTTMessage
    if not MQTT_AVAILABLE:
        raise click.ClickException("paho-mqtt is not installed")
    from .process import REGISTRAR_BOOT_SUFFIX
    from .utils.configuration import (get_namespace,
                                      get_transport_configuration)
    config = get_transport_configuration()
    message = MQTTMessage(host=config.host, port=config.port,
                          username=config.username,
                          password=config.password, tls=config.tls)
    message.connect()
    if not message.connected():
        message.disconnect()
        raise click.ClickException(
            f"cannot reach broker {config.host}:{config.port}"
            f"{': ' + str(message.stats['last_error']) if message.stats['last_error'] else ''}")
    boot_topic = f"{get_namespace()}/{REGISTRAR_BOOT_SUFFIX}"
    message.publish(boot_topic, "", retain=True, wait=True)
    message.disconnect()
    click.echo(f"cleared retained {boot_topic}")


if __name__ == "__main__":
    main()
