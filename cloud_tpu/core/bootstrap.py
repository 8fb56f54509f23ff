"""Container-side bootstrap: the runtime that replaces generated prologues.

The reference *generated Python text* that picked a tf.distribute strategy
and exec'd the user script inside the remote container
(preprocess.py:117-164).  Here the container ENTRYPOINT is this module:

    python -m cloud_tpu.core.bootstrap \
        --entry-point=train.py --mesh-plan='{"sizes": ...}'

On every host it (1) marks the process as remote (the ``remote()``
contract), (2) initializes ``jax.distributed`` from the env contract
(deploy.py writes it into the TPU-VM startup script), (3) builds the
planned mesh and installs it as the global mesh, then (4) runs the user
script under ``__main__`` semantics.  The same script that called
``run()`` locally re-enters here, hits the ``remote()`` guard, and falls
through to its training code — the "same script runs both places"
contract (reference run.py:31-33).
"""

from __future__ import annotations

import argparse
import logging
import os
import runpy
import sys

logger = logging.getLogger(__name__)

ENV_RUNNING_REMOTELY = "CLOUD_TPU_RUNNING_REMOTELY"


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--entry-point", required=True,
                        help=".py or .ipynb to execute under the mesh")
    parser.add_argument("--mesh-plan", default=None,
                        help="MeshPlan JSON (omit: plan over local devices)")
    parser.add_argument("--distribution-strategy", default="auto",
                        choices=["auto", "none"],
                        help="'none': user script owns mesh construction")
    parser.add_argument("entry_point_args", nargs="*",
                        help="argv passed through to the entry point")
    args = parser.parse_args(argv)

    os.environ[ENV_RUNNING_REMOTELY] = "1"

    # Preemption drain: Cloud TPU evictions deliver SIGTERM with a grace
    # window; the handler sets a stop event Trainer.fit checks at every
    # dispatch boundary, so training checkpoints and exits (status
    # PREEMPTION_EXIT_CODE below) instead of dying mid-step.
    from cloud_tpu.training import preemption

    preemption.install_sigterm_handler()

    # Chaos parity across processes: a fault plan exported by
    # faults.inject() in the submitting/test process
    # (CLOUD_TPU_FAULT_PLAN) is re-installed here, so a bootstrapped
    # child or the cloud_fit server injects the same plan.
    from cloud_tpu.utils import faults

    faults.maybe_install_from_env()

    from cloud_tpu.parallel import distributed

    distributed.initialize_from_env()

    # Env-gated observability, mirroring the reference's registered
    # exporter (stackdriver_exporter.cc:31-36,128): the job spec turns
    # these on per-host via CLOUD_TPU_MONITORING_ENABLED /
    # CLOUD_TPU_PROFILER_PORT.
    from cloud_tpu import monitoring

    try:
        if monitoring.start_exporter():
            # The native timer thread calls back into Python; it must be
            # joined before interpreter finalization or the next tick
            # aborts in PyGILState_Ensure.  atexit also covers user
            # scripts that sys.exit().
            import atexit

            atexit.register(monitoring.stop_exporter)
    except Exception:
        # Misconfigured monitoring must not kill the training job.
        logger.exception("metrics exporter failed to start")
    monitoring.profiler.maybe_start_server_from_env()

    # Persistent compile cache (JAX_COMPILATION_CACHE_DIR, else
    # CLOUD_TPU_COMPILE_CACHE as forwarded by deploy's startup script):
    # enabled BEFORE the user script compiles anything, so a
    # preemption-restarted container warm-starts its step executables
    # from disk instead of recompiling from scratch.
    try:
        from cloud_tpu.training import compile_cache

        compile_cache.maybe_enable_persistent_cache()
    except Exception:  # noqa: BLE001 — cache is an optimization, never fatal
        logger.exception("persistent compile cache setup failed; continuing")

    entry_point = args.entry_point
    if entry_point.endswith(".ipynb"):
        from cloud_tpu.core import notebook

        entry_point = notebook.notebook_to_script(entry_point)

    sys.argv = [entry_point] + list(args.entry_point_args)

    if args.distribution_strategy == "none":
        # User-owned parallelism (reference validate.py:117-124 None path).
        runpy.run_path(entry_point, run_name="__main__")
        _exit_if_drained()
        return

    import jax

    from cloud_tpu.parallel import mesh as mesh_lib
    from cloud_tpu.parallel import planner

    if args.mesh_plan:
        plan = planner.MeshPlan.from_json(args.mesh_plan)
    else:
        plan = planner.plan_mesh(num_devices=len(jax.devices()))
    logger.info("bootstrap: %s", plan.description)
    mesh = plan.build()
    with mesh_lib.use_mesh(mesh):
        runpy.run_path(entry_point, run_name="__main__")
    _exit_if_drained()


def _exit_if_drained() -> None:
    """Exit with the distinct preemption status when the user script
    finished BECAUSE the drain stop event fired: the supervisor (and any
    orchestrator reading exit codes) can tell "checkpointed and yielded
    to preemption" (143) apart from success (0) and a crash (!= 0,
    != 143) — the recreate path resumes from the drained checkpoint."""
    from cloud_tpu.training import preemption

    if preemption.stop_requested():
        logger.warning(
            "bootstrap exiting with preemption-drain status %d (%s)",
            preemption.PREEMPTION_EXIT_CODE, preemption.stop_reason(),
        )
        sys.exit(preemption.PREEMPTION_EXIT_CODE)


if __name__ == "__main__":
    main()
