"""Local end-to-end integration: run() artifacts drive a real bootstrap.

SURVEY.md §7.3's minimum-slice checkpoint: with a fake backend (virtual
CPU devices), ``run(entry_point='mnist.py')`` executes end-to-end
locally.  The submit half produces the artifacts under ``dry_run``; the
container half is the real ``cloud_tpu.core.bootstrap`` CLI run as a
subprocess with the produced mesh plan — exactly the ENTRYPOINT the
Dockerfile encodes, minus the docker daemon.  The virtual-mesh rig lives
in ``cloud_tpu.utils.local_rig``.

Reference analogue: core/tests/integration/run_on_script_test.py, which
needed a real GCP project; the GCP-gated equivalents live in
test_run_gcp.py.
"""

import json
import os

import numpy as np

import cloud_tpu
from cloud_tpu.core.containerize import DockerConfig
from cloud_tpu.utils import local_rig

TESTDATA = os.path.join(local_rig.REPO_ROOT, "tests", "testdata")
MNIST = os.path.join(TESTDATA, "mnist_example_using_fit.py")


def _mnist_env(tmp_path):
    return {
        "MNIST_EXAMPLE_EPOCHS": "2",  # the workload asserts loss improves
        "MNIST_EXAMPLE_STEPS": "4",
        "MNIST_EXAMPLE_SAVE_DIR": str(tmp_path),
    }


class TestLocalEndToEnd:
    def test_submit_artifacts_then_bootstrap_trains(self, tmp_path):
        report = cloud_tpu.run(
            entry_point=MNIST,
            chief_config=cloud_tpu.COMMON_MACHINE_CONFIGS["TPU"],
            docker_config=DockerConfig(image="gcr.io/p/e2e:t"),
            dry_run=True,
        )
        assert report.dockerfile and report.mesh_plan is not None
        # The ENTRYPOINT the Dockerfile encodes, executed locally.
        result = local_rig.run_bootstrap(
            MNIST,
            mesh_plan_json=report.mesh_plan.to_json(),
            extra_env=_mnist_env(tmp_path),
        )
        assert result.returncode == 0, result.stdout + result.stderr
        history = json.loads((tmp_path / "history.json").read_text())
        assert np.isfinite(history["loss"][-1])

    def test_bootstrap_monitoring_enabled_exits_cleanly(self, tmp_path):
        # CLOUD_TPU_MONITORING_ENABLED without a project must not kill the
        # job (bootstrap catches it), and with the native thread running
        # the process must still exit 0 (the atexit join).
        env = _mnist_env(tmp_path)
        env["CLOUD_TPU_MONITORING_ENABLED"] = "1"
        result = local_rig.run_bootstrap(MNIST, extra_env=env)
        assert result.returncode == 0, result.stdout + result.stderr

    def test_notebook_entry_point_bootstrap(self, tmp_path):
        result = local_rig.run_bootstrap(
            os.path.join(TESTDATA, "mnist_example_using_fit.ipynb"),
            extra_env=_mnist_env(tmp_path),
        )
        # The notebook's last cell asserts its training loss is finite;
        # exit 0 therefore means conversion + mesh + training all worked.
        assert result.returncode == 0, result.stdout + result.stderr

    def test_records_streaming_workload_through_bootstrap(self, tmp_path):
        """The streaming-input golden workload (BASELINE config 5) runs
        through the real container ENTRYPOINT on the virtual mesh: record
        shards on disk -> RecordDataset -> prefetch -> Trainer.fit under
        the bootstrap-installed mesh."""
        entry = os.path.join(TESTDATA, "records_streaming_example.py")
        report = cloud_tpu.run(
            entry_point=entry,
            chief_config=cloud_tpu.COMMON_MACHINE_CONFIGS["TPU"],
            docker_config=DockerConfig(image="gcr.io/p/rec:t"),
            dry_run=True,
        )
        result = local_rig.run_bootstrap(
            entry,
            mesh_plan_json=report.mesh_plan.to_json(),
            extra_env={
                "RECORDS_EXAMPLE_DIR": str(tmp_path / "data"),
                "RECORDS_EXAMPLE_SAVE": str(tmp_path),
            },
        )
        assert result.returncode == 0, result.stdout + result.stderr
        history = json.loads((tmp_path / "history.json").read_text())
        assert history["loss"][-1] < history["loss"][0]

    def test_within_script_contract_remote_half(self, tmp_path):
        # Script mode, container side: the remote() guard makes run()
        # return immediately and the training below executes (the local
        # sys.exit(0) half is unit-tested in test_launcher.py).
        script = tmp_path / "self_launch.py"
        script.write_text(
            "import cloud_tpu\n"
            "from cloud_tpu.core.containerize import DockerConfig\n"
            "cloud_tpu.run(\n"
            "    chief_config=cloud_tpu.COMMON_MACHINE_CONFIGS['TPU'],\n"
            "    docker_config=DockerConfig(image='gcr.io/p/self:t'),\n"
            ")\n"
            "print('TRAINED')\n"
        )
        remote = local_rig.run_bootstrap(
            str(script), extra_env=_mnist_env(tmp_path)
        )
        assert remote.returncode == 0, remote.stdout + remote.stderr
        assert "TRAINED" in remote.stdout
