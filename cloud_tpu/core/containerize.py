"""Containerization: TPU-ready Dockerfile synthesis + image builders.

Reference analogue: ``src/python/tensorflow_cloud/core/containerize.py``
(Dockerfile synthesis :134-228, build-context tar :124-132/:235-277,
LocalContainerBuilder :304-383, CloudContainerBuilder :386-507).

TPU-native differences:

* Base images are plain Python (TPU VMs need no CUDA base): the Dockerfile
  installs ``jax[tpu]`` from the libtpu release index instead of choosing
  ``-gpu`` tags (reference :134-158's DockerHub probing disappears).
* The ENTRYPOINT is the bootstrap runtime
  (``python -m cloud_tpu.core.bootstrap``), not a preprocessed script.
* The docker SDK dependency is replaced by the docker CLI via subprocess
  (injectable for tests), and Cloud Build is driven through the plain REST
  session from ``utils/api_client.py``.
"""

from __future__ import annotations

import abc
import io
import json
import logging
import os
import shutil
import subprocess
import tarfile
import tempfile
import time
import uuid
from dataclasses import dataclass
from typing import Callable, List, Optional

from cloud_tpu.core import gcp, machine_config
from cloud_tpu.utils import api_client

logger = logging.getLogger(__name__)

LIBTPU_INDEX = "https://storage.googleapis.com/jax-releases/libtpu_releases.html"


def default_base_image() -> str:
    """``python:<local major.minor>-slim``.

    Derived from the SUBMITTING interpreter the way the reference derived
    its base image from the local TF version (containerize.py:134-158) —
    cloud_fit ships cloudpickled closures whose bytecode only loads on the
    same Python minor, so client and container must match by construction.
    """
    import sys

    return f"python:{sys.version_info.major}.{sys.version_info.minor}-slim"


def default_jax_pin() -> Optional[str]:
    """``jax==<local jax.__version__>`` — client/container version lock.

    The reference pinned the container's TF to the local TF (its whole
    base-image selection, :134-158, existed for this); SURVEY §7 step 4
    says "pin libtpu/JAX versions".  An unpinned ``jax[tpu]`` would make
    the pod run whatever shipped that day, and serialized artifacts
    (cloud_fit closures, mesh-plan JSON, checkpoints) are exactly what
    breaks under skew.  jax's libtpu requirement is itself pinned by the
    jax wheel, so pinning jax pins libtpu transitively.

    Returns None (=> install unpinned, with a warning) when the local jax
    is a dev/source build whose version has no PyPI release to pin to —
    the reference's nightly fallback (:160-185) for the same situation.

    When jax is already imported, its ``__version__`` is the truth (an
    editable/source checkout shadowing an installed wheel must not be
    pinned to the stale dist-info).  Otherwise read the distribution
    metadata rather than importing: a cold ``import jax`` costs ~1.5-2 s,
    which would triple run()'s submit-artifacts latency (half of
    BASELINE.json's north star) just to learn a version string.
    """
    import sys

    version = getattr(sys.modules.get("jax"), "__version__", None)
    if version is None:
        try:
            import importlib.metadata

            version = importlib.metadata.version("jax")
        except Exception:  # noqa: BLE001 — source trees without dist-info
            import jax

            version = jax.__version__
    if "dev" in version or "+" in version:
        logger.warning(
            "local jax %s is a dev/source build with no released wheel; "
            "container installs UNPINNED jax — set "
            "DockerConfig(jax_version=...) to pin explicitly",
            version,
        )
        return None
    return f"jax=={version}"
_CLOUD_BUILD_POLL_INTERVAL_SECONDS = 30
_CLOUD_BUILD_POLL_ATTEMPTS = 20  # reference budget: 20 x 30s (:390,432-453)


@dataclass
class DockerConfig:
    """User knobs for image naming and building (reference run.py docker_config)."""

    image: Optional[str] = None  # full target URI; default gcr.io/<proj>/...
    parent_image: Optional[str] = None  # overrides default_base_image()
    cache_from: Optional[str] = None  # warm-layer source image
    image_build_bucket: Optional[str] = None  # GCS bucket => Cloud Build
    jax_version: Optional[str] = None  # e.g. "0.9.1"; default = local jax


def make_dockerfile(
    entry_point_name: str,
    chief_config: machine_config.MachineConfig,
    *,
    requirements_name: Optional[str] = None,
    parent_image: Optional[str] = None,
    mesh_plan_json: Optional[str] = None,
    distribution_strategy: str = "auto",
    entry_point_args: Optional[List[str]] = None,
    jax_version: Optional[str] = None,
) -> str:
    """Render the Dockerfile text (golden-tested, like reference :134-228).

    ``jax_version`` overrides the container's jax pin (a bare version
    string like "0.9.1"); default pins to the submitting client's local
    jax so local and remote provably match (see :func:`default_jax_pin`).
    """
    pin = f"jax=={jax_version}" if jax_version else default_jax_pin()
    lines = [f"FROM {parent_image or default_base_image()}", "WORKDIR /app"]
    if machine_config.is_tpu_config(chief_config):
        spec = (
            pin.replace("jax==", "jax[tpu]==", 1) if pin else "jax[tpu]"
        )
        lines.append(f"RUN pip install --no-cache-dir '{spec}' -f {LIBTPU_INDEX}")
    else:
        lines.append(f"RUN pip install --no-cache-dir '{pin or 'jax'}'")
    if requirements_name:
        lines.append(f"COPY {requirements_name} /app/{requirements_name}")
        lines.append(
            f"RUN pip install --no-cache-dir -r /app/{requirements_name}"
        )
    # The build context vendors the framework tree (the reference pip-
    # installed tensorflow-cloud, :208-209; vendoring pins the image to the
    # submitting client's exact version).
    lines.append("COPY . /app")
    lines.append('ENV PYTHONPATH="/app:${PYTHONPATH}"')
    entrypoint = [
        "python",
        "-m",
        "cloud_tpu.core.bootstrap",
        f"--entry-point={entry_point_name}",
        f"--distribution-strategy={distribution_strategy}",
    ]
    if mesh_plan_json:
        entrypoint.append(f"--mesh-plan={mesh_plan_json}")
    if entry_point_args:
        entrypoint.append("--")  # bootstrap passes the rest to the script
        entrypoint.extend(entry_point_args)
    # json.dumps produces the exec-form array with correct escaping — the
    # mesh-plan JSON contains quotes that naive formatting would corrupt
    # (Docker would silently fall back to shell form).
    lines.append(f"ENTRYPOINT {json.dumps(entrypoint)}")
    return "\n".join(lines) + "\n"


def default_image_uri(project: str) -> str:
    """gcr.io/<project>/cloud_tpu_train:<uuid> (reference :279-285)."""
    return f"gcr.io/{project}/cloud_tpu_train:{uuid.uuid4().hex[:12]}"


def build_context(
    dockerfile_text: str,
    entry_point: Optional[str],
    requirements_txt: Optional[str],
    dst_dir: Optional[str] = None,
) -> str:
    """Assemble the docker build context directory.

    Contents: Dockerfile, the entry point's whole directory (multi-file
    projects work, reference tests/examples/multi_file_example), optional
    requirements, and the cloud_tpu framework tree.
    """
    if dst_dir is None:
        dst_dir = tempfile.mkdtemp(prefix="cloud_tpu_ctx_")
    os.makedirs(dst_dir, exist_ok=True)
    with open(os.path.join(dst_dir, "Dockerfile"), "w") as f:
        f.write(dockerfile_text)
    if entry_point is not None:
        src_dir = os.path.dirname(os.path.abspath(entry_point)) or "."
        for name in os.listdir(src_dir):
            src = os.path.join(src_dir, name)
            dst = os.path.join(dst_dir, name)
            if name in ("Dockerfile", "cloud_tpu") or name.startswith("."):
                continue
            if os.path.isdir(src):
                if not os.path.exists(dst):
                    shutil.copytree(src, dst)
            else:
                shutil.copy2(src, dst)
    if requirements_txt is not None:
        shutil.copy2(
            requirements_txt,
            os.path.join(dst_dir, os.path.basename(requirements_txt)),
        )
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg_dst = os.path.join(dst_dir, "cloud_tpu")
    if not os.path.exists(pkg_dst):
        shutil.copytree(
            pkg_root, pkg_dst,
            ignore=shutil.ignore_patterns("__pycache__", "*.pyc", "*.so"),
        )
    return dst_dir


class ContainerBuilder(abc.ABC):
    """Build + publish an image, returning its URI (reference :44-301)."""

    def __init__(self, image_uri: str, context_dir: str):
        self.image_uri = image_uri
        self.context_dir = context_dir

    @abc.abstractmethod
    def get_docker_image(self) -> str: ...


class LocalContainerBuilder(ContainerBuilder):
    """docker CLI build + push (reference drove the docker SDK, :304-383).

    ``runner`` is injectable: signature ``(argv: List[str]) -> None``; tests
    substitute a recorder.
    """

    def __init__(self, image_uri, context_dir, *,
                 cache_from: Optional[str] = None,
                 runner: Optional[Callable[[List[str]], None]] = None):
        super().__init__(image_uri, context_dir)
        self.cache_from = cache_from
        self._runner = runner or self._run_streaming

    @staticmethod
    def _run_streaming(argv: List[str]) -> None:
        logger.info("$ %s", " ".join(argv))
        proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True
        )
        assert proc.stdout is not None
        for line in proc.stdout:
            logger.info("%s", line.rstrip())
        if proc.wait() != 0:
            raise RuntimeError(f"Command failed ({proc.returncode}): {argv}")

    def get_docker_image(self) -> str:
        build = ["docker", "build", "-t", self.image_uri]
        if self.cache_from:
            build += ["--cache-from", self.cache_from]
        build.append(self.context_dir)
        self._runner(build)
        self._runner(["docker", "push", self.image_uri])
        return self.image_uri


class CloudContainerBuilder(ContainerBuilder):
    """GCS-upload + Cloud Build (reference :386-507), REST via the
    injectable session."""

    def __init__(self, image_uri, context_dir, *, project: str, bucket: str,
                 session: Optional[api_client.GcpApiSession] = None,
                 storage_client=None,
                 sleeper: Callable[[float], None] = time.sleep):
        super().__init__(image_uri, context_dir)
        self.project = project
        self.bucket = bucket
        self._session = session
        self._storage_client = storage_client
        self._sleep = sleeper

    def _tarball(self) -> bytes:
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tar:
            tar.add(self.context_dir, arcname=".")
        return buf.getvalue()

    def _upload_context(self) -> str:
        object_name = f"cloud_tpu_build/{uuid.uuid4().hex}.tgz"
        client = self._storage_client
        if client is None:
            from google.cloud import storage

            client = storage.Client(project=self.project)
        blob = client.bucket(self.bucket).blob(object_name)
        blob.upload_from_string(self._tarball(), content_type="application/gzip")
        return object_name

    def build_request(self, object_name: str) -> dict:
        """The Cloud Build request body (golden-tested, reference :481-507)."""
        return {
            "source": {
                "storageSource": {
                    "bucket": self.bucket,
                    "object": object_name,
                }
            },
            "steps": [
                {
                    "name": "gcr.io/cloud-builders/docker",
                    "args": ["build", "-t", self.image_uri, "."],
                }
            ],
            "images": [self.image_uri],
        }

    def get_docker_image(self) -> str:
        session = self._session or api_client.default_session()
        object_name = self._upload_context()
        url = f"https://cloudbuild.googleapis.com/v1/projects/{self.project}/builds"
        op = session.post(url, body=self.build_request(object_name))
        build_id = op.get("metadata", {}).get("build", {}).get("id")
        if not build_id:
            raise RuntimeError(f"Cloud Build returned no build id: {op}")
        status_url = (
            f"https://cloudbuild.googleapis.com/v1/projects/{self.project}"
            f"/builds/{build_id}"
        )
        for _ in range(_CLOUD_BUILD_POLL_ATTEMPTS):
            build = session.get(status_url)
            status = build.get("status")
            if status == "SUCCESS":
                return self.image_uri
            if status in ("FAILURE", "INTERNAL_ERROR", "TIMEOUT", "CANCELLED"):
                raise RuntimeError(f"Cloud Build {build_id} failed: {status}")
            self._sleep(_CLOUD_BUILD_POLL_INTERVAL_SECONDS)
        raise TimeoutError(
            f"Cloud Build {build_id} did not finish within "
            f"{_CLOUD_BUILD_POLL_ATTEMPTS * _CLOUD_BUILD_POLL_INTERVAL_SECONDS}s"
        )
