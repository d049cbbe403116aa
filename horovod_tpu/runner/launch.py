"""``hvdrun`` — the launcher CLI.

TPU-native rebuild of the reference's ``horovodrun``
(``/root/reference/horovod/runner/launch.py:242-775``): parse host/slot
topology, seed per-worker env (rank layout + rendezvous coordinates), spawn
one controller process per slot — locally or over ssh — and supervise the
job. The gloo/MPI controller split disappears: workers rendezvous through
``jax.distributed`` (coordinator = rank-0 host) plus the launcher's HTTP KV
store (results, elastic notifications).

Static path mirrors ``_run_static`` (``launch.py:530-620``); elastic path
mirrors ``_run_elastic`` (``launch.py:623-672``) and is implemented in
``horovod_tpu.elastic``.
"""

from __future__ import annotations

import argparse
import functools
import os
import shlex
import socket
import subprocess
import sys
import threading

from . import hosts as hosts_mod
from . import safe_exec
from .http_kv import KVServer, local_addresses, make_secret
from ..utils import envs
from ..version import __version__

SSH_OPTIONS = ["-o", "PasswordAuthentication=no",
               "-o", "StrictHostKeyChecking=no",
               "-o", "ConnectTimeout=10"]

# env vars forwarded from the launcher environment to every worker
# (reference forwards the full env over ssh via env exports,
# gloo_run.py:114-199)
_FORWARD_PREFIXES = ("HVD_", "HOROVOD_", "JAX_", "XLA_", "TPU_", "LIBTPU_",
                     "PYTHON", "PATH", "LD_", "VIRTUAL_ENV", "HOME", "USER",
                     "CUDA_", "TF_", "NCCL_")


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu distributed job.",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-v", "--version", action="version",
                        version=f"hvdrun {__version__}")
    parser.add_argument("-np", "--num-proc", dest="np", type=int, default=None,
                        help="total number of worker processes")
    parser.add_argument("-H", "--hosts", default=None,
                        help='host list, e.g. "h1:2,h2:2" (slots default 1)')
    parser.add_argument("--hostfile", default=None,
                        help='hostfile with "hostname slots=N" lines')
    parser.add_argument("--slots-per-host", type=int, default=None,
                        help="override slot count for every host")
    parser.add_argument("--min-np", type=int, default=None,
                        help="elastic: minimum world size")
    parser.add_argument("--max-np", type=int, default=None,
                        help="elastic: maximum world size")
    parser.add_argument("--host-discovery-script", default=None,
                        help="elastic: executable printing one host:slots per line")
    parser.add_argument("--reset-limit", type=int, default=None,
                        help="elastic: stop after this many resets")
    parser.add_argument("--blacklist-cooldown-range", nargs=2, type=float,
                        default=None, metavar=("LO", "HI"),
                        help="elastic: blacklisted-host cooldown bounds (s)")
    parser.add_argument("--ssh-port", type=int, default=None)
    parser.add_argument("--ssh-identity-file", default=None)
    parser.add_argument("--start-timeout", type=float, default=600.0,
                        help="seconds to wait for the job to start")
    parser.add_argument("--output-filename", default=None,
                        help="redirect per-rank output to <dir>/rank.<N>/stdout|stderr")
    parser.add_argument("--coordinator-port", type=int, default=0,
                        help="port for jax.distributed coordinator (0 = auto)")
    parser.add_argument("--config-file", default=None,
                        help="YAML config file (CLI flags win)")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("--disable-cache", action="store_true",
                        help="set HVD_CACHE_CAPACITY=0 in workers")
    parser.add_argument("--timeline-filename", default=None)
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="expose each worker's Prometheus /metrics "
                             "on this base port + its rank (seeds "
                             "HVD_METRICS_PORT; docs/metrics.md). The "
                             "launcher KV server always serves its own "
                             "/metrics route")
    parser.add_argument("--autotune", action="store_true")
    parser.add_argument("--env", action="append", default=[],
                        metavar="NAME=VALUE", help="extra env for workers")
    parser.add_argument("--loopback", action="store_true",
                        help="run all ranks as threads in ONE interpreter "
                             "over the in-process loopback engine "
                             "(hvd.loopback; docs/loopback.md) — the "
                             "world>1 stack without spawning a process "
                             "or building a cross-process XLA program")
    parser.add_argument("--launcher", choices=("auto", "local", "lsf"),
                        default="auto",
                        help="host-source escape hatch: 'auto' derives "
                             "hosts from a detected LSF allocation when no "
                             "-H/--hostfile is given, 'local' ignores "
                             "scheduler env, 'lsf' requires an LSF "
                             "allocation and fails loudly without one")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the training command")
    args = parser.parse_args(argv)

    if args.config_file:
        from . import config_parser
        cfg = config_parser.load_config(args.config_file)
        explicit = _explicit_dests(argv if argv is not None else sys.argv[1:], parser)
        config_parser.apply_config_to_args(cfg, args, explicit)
        args._config_env = config_parser.config_to_env(cfg)
    else:
        args._config_env = {}
    return args


def _explicit_dests(argv, parser) -> set:
    """Dest names of launcher options actually present on the command line.

    Scanning stops at ``--`` or at the first token that starts the training
    command, so flag lookalikes inside the command (e.g. the user's own
    ``--verbose``) are not misclassified as launcher options."""
    explicit = set()
    opt_actions = {}
    for action in parser._actions:
        for opt in action.option_strings:
            opt_actions[opt] = action
    i = 0
    while i < len(argv):
        tok = argv[i]
        if tok == "--":
            break
        if tok.startswith("-"):
            opt = tok.split("=", 1)[0]
            action = opt_actions.get(opt)
            if action is None and opt.startswith("--"):
                # argparse accepts unambiguous long-option abbreviations
                matches = {a for o, a in opt_actions.items()
                           if o.startswith(opt)}
                if len(matches) == 1:
                    action = next(iter(matches))
            if action is None:
                break  # unknown flag: the training command has started
            explicit.add(action.dest)
            if "=" in tok or isinstance(action, (
                    argparse._StoreTrueAction, argparse._StoreFalseAction,
                    argparse._CountAction, argparse._HelpAction,
                    argparse._VersionAction)):
                consumed = 0
            elif isinstance(action.nargs, int):
                consumed = action.nargs  # e.g. --blacklist-cooldown-range LO HI
            else:
                consumed = 1
            i += 1 + consumed
            continue
        break  # first positional token: the training command has started
    return explicit


def _resolve_hosts(args) -> list[hosts_mod.HostSpec]:
    from . import lsf

    if args.hosts and args.hostfile:
        raise ValueError("--hosts and --hostfile are mutually exclusive")
    launcher = getattr(args, "launcher", "auto")
    if launcher == "lsf" and not lsf.using_lsf():
        raise RuntimeError("--launcher lsf: no LSF allocation detected "
                           "(LSB_JOBID not set)")
    specs = None
    if args.hosts:
        specs = hosts_mod.parse_hosts(args.hosts)
    elif args.hostfile:
        specs = hosts_mod.parse_hostfile(args.hostfile)
    elif launcher != "local" and lsf.using_lsf():
        # hvdrun inside an LSF allocation: hosts come from the allocation
        # itself (reference launch.py does the same via LSFUtils)
        try:
            specs = lsf.lsf_host_specs()
        except RuntimeError:
            if launcher == "lsf":
                raise  # explicitly requested: fail loudly
            # auto: LSB_JOBID present but no usable host env — fall through
    if specs is None:
        specs = [hosts_mod.HostSpec("localhost", args.np or 1)]
    if args.slots_per_host:
        specs = [hosts_mod.HostSpec(h.hostname, args.slots_per_host)
                 for h in specs]
    return specs


_is_local_cache: dict[str, bool] = {}


def is_local_host(hostname: str) -> bool:
    if hostname in ("localhost", "127.0.0.1", socket.gethostname()):
        return True
    cached = _is_local_cache.get(hostname)
    if cached is not None:
        return cached
    try:
        result = socket.gethostbyname(hostname) in local_addresses()
    except OSError:
        return False  # transient resolver failure: do NOT memoize
    _is_local_cache[hostname] = result
    return result


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _forwarded_env() -> dict[str, str]:
    env = {}
    for k, v in os.environ.items():
        if k.startswith(_FORWARD_PREFIXES):
            env[k] = v
    # Make sure workers can import this package even when it is not
    # pip-installed and the worker script lives elsewhere (reference relies
    # on horovod being installed on every host; we forward the import root).
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parts = os.environ.get("PYTHONPATH", "").split(os.pathsep)
    if pkg_root not in parts:
        parts.insert(0, pkg_root)
    env["PYTHONPATH"] = os.pathsep.join(p for p in parts if p)
    return env


def worker_env(slot: hosts_mod.SlotInfo, *, coordinator_addr: str,
               coordinator_port: int, kv_addr: str, kv_port: int,
               secret: str, extra: dict | None = None) -> dict[str, str]:
    """Seed one worker's env (reference seeds HOROVOD_RANK/... at
    ``gloo_run.py:65-101,201-226``)."""
    env = _forwarded_env()
    env.update({
        "HVD_RANK": str(slot.rank),
        "HVD_SIZE": str(slot.size),
        "HVD_LOCAL_RANK": str(slot.local_rank),
        "HVD_LOCAL_SIZE": str(slot.local_size),
        "HVD_CROSS_RANK": str(slot.cross_rank),
        "HVD_CROSS_SIZE": str(slot.cross_size),
        "HVD_PROCESS_ID": str(slot.rank),
        "HVD_NUM_PROCESSES": str(slot.size),
        "HVD_COORDINATOR_ADDR": coordinator_addr,
        "HVD_COORDINATOR_PORT": str(coordinator_port),
        "HVD_KV_ADDR": kv_addr,
        "HVD_KV_PORT": str(kv_port),
        "HVD_SECRET_KEY": secret,
        "HVD_HOSTNAME": slot.hostname,
    })
    if extra:
        env.update(extra)
    return env


# Env vars whose values must never appear in an ssh argv (visible to every
# local user via ps). The reference excludes the secret from ssh-exported env
# the same way (``runner/common/util/env.py:24`` IGNORE_REGEXES); we deliver
# it over the ssh channel's stdin instead.
_SECRET_ENV_VARS = ("HVD_SECRET_KEY",)


def _ssh_base_cmd(ssh_port: int | None, identity_file: str | None) -> list[str]:
    return (["ssh"] + SSH_OPTIONS
            + (["-p", str(ssh_port)] if ssh_port else [])
            + (["-i", identity_file] if identity_file else []))


def _ssh_command(hostname: str, command: list[str], env: dict[str, str],
                 ssh_port: int | None, identity_file: str | None) -> list[str]:
    public_env = {k: v for k, v in env.items() if k not in _SECRET_ENV_VARS}
    exports = " ".join(f"export {k}={shlex.quote(v)};"
                       for k, v in public_env.items())
    secret_reads = " ".join(f"IFS= read -r {k}; export {k};"
                            for k in _SECRET_ENV_VARS if k in env)
    remote = (f"cd {shlex.quote(os.getcwd())} 2>/dev/null; {secret_reads} "
              f"{exports} " + " ".join(shlex.quote(c) for c in command))
    return _ssh_base_cmd(ssh_port, identity_file) + [hostname, remote]


def spawn_worker(slot: hosts_mod.SlotInfo, command: list[str],
                 env: dict[str, str], args) -> safe_exec.ExecutedProcess:
    stdout = stderr = None
    owned = []
    if args.output_filename:
        d = os.path.join(args.output_filename, f"rank.{slot.rank}")
        os.makedirs(d, exist_ok=True)
        stdout = open(os.path.join(d, "stdout"), "w")
        stderr = open(os.path.join(d, "stderr"), "w")
        owned = [stdout, stderr]
    if is_local_host(slot.hostname):
        full_env = dict(os.environ)
        full_env.update(env)
        return safe_exec.execute(command, env=full_env, index=slot.rank,
                                 stdout=stdout, stderr=stderr, owned_files=owned)
    cmd = _ssh_command(slot.hostname, command, env,
                       args.ssh_port, args.ssh_identity_file)
    secret_lines = b"".join(env[k].encode() + b"\n"
                            for k in _SECRET_ENV_VARS if k in env)
    return safe_exec.execute(cmd, env=dict(os.environ), index=slot.rank,
                             stdout=stdout, stderr=stderr, shell=False,
                             stdin_data=secret_lines or None, owned_files=owned)


def probe_remote_free_port(hostname: str, ssh_port=None,
                           identity_file=None, timeout: float = 20) -> int:
    """Ask ``hostname``'s kernel for a free ephemeral port over ssh.

    Used for the remote jax.distributed coordinator endpoint: a
    kernel-assigned ephemeral port is vastly less collision-prone than a
    blind random pick (the kernel avoids ports in use and cycles the
    ephemeral range). Raises on ssh failure or unparsable output."""
    probe = ("python3 -c 'import socket; s=socket.socket(); "
             "s.bind((\"\", 0)); print(s.getsockname()[1])'")
    cmd = _ssh_base_cmd(ssh_port, identity_file) + [hostname, probe]
    out = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, timeout=timeout, env=dict(os.environ))
    if out.returncode != 0:
        raise RuntimeError(
            f"port probe on {hostname} failed: {out.stderr.strip()[:500]}")
    return int(out.stdout.strip().splitlines()[-1])


def check_hosts_ssh(hostnames: list[str], ssh_port=None,
                    identity_file=None) -> None:
    """Fail fast when a remote host is unreachable (reference
    ``_check_all_hosts_ssh_successful``, ``launch.py:58-108``)."""
    remote = [h for h in hostnames if not is_local_host(h)]
    failures = []

    def check(h):
        cmd = _ssh_base_cmd(ssh_port, identity_file) + [h, "true"]
        if safe_exec.run(cmd, env=dict(os.environ), prefix_output=False) != 0:
            failures.append(h)

    threads = [threading.Thread(target=check, args=(h,)) for h in set(remote)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        raise RuntimeError(f"ssh connection failed for hosts: {sorted(failures)}")


class JobRendezvous:
    """Shared rendezvous state for one job: the launcher-side KV server and
    the coordinator address workers will dial."""

    def __init__(self, slots: list[hosts_mod.SlotInfo],
                 coordinator_port: int = 0):
        self.secret = make_secret()
        self.kv = KVServer(secret=self.secret)
        self.kv_port = self.kv.start()
        all_local = all(is_local_host(s.hostname) for s in slots)
        self.kv_addr = "127.0.0.1" if all_local else local_addresses()[0]
        # jax.distributed coordinator lives in rank 0's process on rank 0's
        # host, so that is the address every worker must dial.
        coord_host = slots[0].hostname
        self.coord_addr = "127.0.0.1" if all_local else (
            self.kv_addr if is_local_host(coord_host) else coord_host)
        self.coord_port = coordinator_port or _free_port()

    def worker_env(self, slot, extra=None) -> dict[str, str]:
        return worker_env(
            slot, coordinator_addr=self.coord_addr,
            coordinator_port=self.coord_port, kv_addr=self.kv_addr,
            kv_port=self.kv_port, secret=self.secret, extra=extra)

    def stop(self) -> None:
        self.kv.stop()


def run_static(args, command: list[str]) -> int:
    """Spawn all ranks, wait; first failure tears the job down
    (reference ``_run_static`` + ``launch_gloo``)."""
    specs = _resolve_hosts(args)
    np = args.np or hosts_mod.total_slots(specs)
    slots = hosts_mod.get_host_assignments(specs, np)
    check_hosts_ssh([s.hostname for s in slots],
                    args.ssh_port, args.ssh_identity_file)

    rdv = JobRendezvous(slots, args.coordinator_port)

    extra = dict(args._config_env)
    for assignment in args.env:
        k, _, v = assignment.partition("=")
        extra[k] = v
    if args.disable_cache:
        extra["HVD_CACHE_CAPACITY"] = "0"
    if args.timeline_filename:
        extra["HVD_TIMELINE"] = args.timeline_filename
    if args.metrics_port:
        extra["HVD_METRICS_PORT"] = str(args.metrics_port)
    if args.autotune:
        extra["HVD_AUTOTUNE"] = "1"

    procs = []
    try:
        for slot in slots:
            procs.append(spawn_worker(slot, command,
                                      rdv.worker_env(slot, extra), args))
        return _supervise(procs, slots, args)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        rdv.stop()


def _supervise(procs, slots, args) -> int:
    """Wait for all workers; kill the job on first failure (reference
    MULTI-process supervision in ``gloo_run.py:114-199``)."""
    exit_codes: dict[int, int] = {}
    lock = threading.Lock()
    failed = threading.Event()

    def waiter(i, p):
        code = p.wait()
        with lock:
            exit_codes[i] = code
        if code != 0:
            failed.set()

    threads = [threading.Thread(target=waiter, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    for t in threads:
        t.start()
    while True:
        with lock:
            if len(exit_codes) == len(procs):
                break
        if failed.wait(timeout=0.2):
            break
    if failed.is_set():
        with lock:
            bad = {slots[i].rank: c for i, c in exit_codes.items() if c != 0}
        for p in procs:
            if p.poll() is None:
                p.terminate()
        print(f"hvdrun: worker failure, exit codes by rank: {bad}",
              file=sys.stderr)
        return next(iter(bad.values()), 1)
    for t in threads:
        t.join()
    return 0


def run_commandline(argv=None) -> int:
    args = parse_args(argv)
    command = list(args.command)
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("hvdrun: no command given", file=sys.stderr)
        return 2
    if args.verbose:
        envs.set_env(envs.LOG_LEVEL, "debug", only_if_unset=True)
    elastic = args.host_discovery_script or args.min_np or args.max_np
    if elastic:
        try:
            from ..elastic.launch import run_elastic
        except ImportError as e:
            print(f"hvdrun: elastic launch unavailable ({e})", file=sys.stderr)
            return 2
        return run_elastic(args, command)
    if args.loopback:
        from ..loopback.engine import run_command as run_loopback
        return run_loopback(args, command)
    return run_static(args, command)


def main() -> None:  # console entry point
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
