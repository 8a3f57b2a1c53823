"""Shared CLI plumbing: logging setup, multi-host runtime initialization."""

from __future__ import annotations

import contextlib
import logging
import os


def maybe_init_distributed() -> bool:
    """Initialize the JAX multi-host runtime when a coordinator is
    configured (the cluster-session bring-up the reference does in
    SparkSessionConfiguration.scala:109; here controller-less multi-host:
    every process calls jax.distributed.initialize and jax.devices() then
    spans all hosts, so the estimator's auto mesh rides ICI/DCN).

    The launch is multi-host only when ``JAX_COORDINATOR_ADDRESS`` is
    set; ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` complete it (with
    all three given, JAX's cluster auto-detection is switched off — on a
    host with TPU chips that detection queries the GCE metadata server,
    which a sealed machine cannot reach). Without a coordinator this
    never calls ``jax.distributed.initialize``: a single-process launch
    touches no network. Returns True when initialization ran.
    """
    from photon_tpu.obs import fleet

    address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not address:
        # The init half of the fleet clock-alignment handshake
        # (obs/fleet.py) is sampled on EVERY path out of here —
        # single-host runs ship 1-rank bundles too.
        fleet.mark_init()
        return False

    import jax

    if jax.distributed.is_initialized():
        fleet.mark_init()
        return False  # idempotent CLI re-entry in one process
    counts = {
        arg: int(os.environ[var])
        for arg, var in (("num_processes", "JAX_NUM_PROCESSES"),
                         ("process_id", "JAX_PROCESS_ID"))
        if var in os.environ
    }
    # A half-configured launch (coordinator set, counts missing and no
    # cluster environment to supply them) raises from initialize: pods
    # silently training independent models would be far worse than
    # failing fast.
    jax.distributed.initialize(
        coordinator_address=address,
        **counts,
        cluster_detection_method="deactivate" if len(counts) == 2 else None,
    )
    fleet.mark_init()
    logging.getLogger("photon.cli").info(
        "multi-host runtime up: process %d/%d, %d global device(s)",
        jax.process_index(), jax.process_count(), len(jax.devices()),
    )
    return True


def fetch_global(x):
    """Materialize a (possibly host-spanning) device array on this host.

    Multi-host meshes shard rows across processes; fetching such an array
    with ``np.asarray`` raises (non-addressable shards). Single-host is a
    plain fetch.
    """
    import jax
    import numpy as np

    if jax.process_count() == 1:
        return np.asarray(x)
    # Process-local (fully addressable) or replicated arrays already carry
    # the complete value on this host: allgathering them would concatenate
    # one full copy per process (duplicated rows in the written output).
    # Only arrays genuinely sharded across hosts need the gather.
    if getattr(x, "is_fully_addressable", True) or getattr(
        x, "is_fully_replicated", False
    ):
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def is_coordinator() -> bool:
    """True on the process that owns artifact writes (process 0).

    Multi-host SPMD runs execute the same driver on every process; model /
    score / summary files must be written once (the reference writes from
    the Spark driver only). Single-host is trivially the coordinator.
    """
    import jax

    return jax.process_index() == 0


@contextlib.contextmanager
def cli_logging(verbose: bool, log_file: str | None,
                fmt: str = "%(asctime)s %(name)s %(levelname)s %(message)s"):
    """Console logging at WARNING (INFO with ``verbose``) plus an optional
    INFO-level file sink (the PhotonLogger equivalent,
    util/PhotonLogger.scala:34). Gating happens at the HANDLER level so the
    file sink can capture INFO without flooding the console, and the file
    handler is detached and closed on exit — repeated ``main()`` calls in
    one process (tests, notebooks) don't leak handlers or level state.
    """
    root = logging.getLogger()
    console = logging.StreamHandler()
    console.setLevel(logging.INFO if verbose else logging.WARNING)
    console.setFormatter(logging.Formatter(fmt))
    handlers = [console]
    if log_file:
        sink = logging.FileHandler(log_file)
        sink.setLevel(logging.INFO)
        sink.setFormatter(logging.Formatter(fmt))
        handlers.append(sink)
    prev_level = root.level
    # INFO records are only materialized when something consumes them.
    root.setLevel(
        logging.INFO if (verbose or log_file) else logging.WARNING)
    for h in handlers:
        root.addHandler(h)
    try:
        yield
    finally:
        for h in handlers:
            root.removeHandler(h)
            h.close()
        root.setLevel(prev_level)
