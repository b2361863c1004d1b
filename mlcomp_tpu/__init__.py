"""mlcomp_tpu — a TPU-native distributed DAG pipeline framework for ML.

Re-imagination of MLComp (reference: /root/reference, catalyst-team MLComp
v19.10.1) designed TPU-first: the training path is JAX/XLA (jit'd steps,
optax, orbax checkpoints, pjit/shard_map over a device Mesh) instead of
Catalyst/torch/NCCL; the scheduler allocates TPU cores/chips instead of GPU
indices; the task transport is a DB-backed queue instead of Celery/Redis.

Environment bootstrap (parity: reference mlcomp/__init__.py:7-106):
- creates ``ROOT_FOLDER`` (default ``~/mlcomp_tpu``) with subfolders
  ``data models tasks logs configs db tmp``
- materializes a default ``.env`` into ``configs/`` on first import and
  exports every variable into ``os.environ``
- builds the DB connection string (sqlite file under ``db/`` by default)
- when running under pytest-xdist (``PYTEST_XDIST_WORKER``), redirects the
  root to a per-worker sandbox so tests are fully isolated
  (parity: reference mlcomp/__init__.py:10-13).
"""

import os
import shutil

__version__ = '0.1.0'

_DEFAULT_ENV = """\
# mlcomp_tpu machine-level configuration.
# Every variable here is exported into the process environment on import.
ROOT_FOLDER=
TOKEN=token
WORKER_TOKEN=
INSTALL_LIBRARIES=False
DB_TYPE=SQLITE
POSTGRES_DB=mlcomp_tpu
POSTGRES_USER=mlcomp_tpu
POSTGRES_PASSWORD=
POSTGRES_HOST=localhost
PGDATA=/var/lib/postgresql/data
QUEUE_POLL_INTERVAL=0.2
WEB_HOST=0.0.0.0
WEB_PORT=4201
WEB_REFRESH_INTERVAL=5000
CONSOLE_LOG_LEVEL=DEBUG
DB_LOG_LEVEL=INFO
FILE_LOG_LEVEL=INFO
LOG_NAME=log
IP=localhost
PORT=4202
MASTER_PORT_RANGE=29500-29510
NCCL_SOCKET_IFNAME=
FILE_SYNC_INTERVAL=300
WORKER_USAGE_INTERVAL=10
SYNC_WITH_THIS_COMPUTER=True
CAN_PROCESS_TASKS=True
TPU_CORES_PER_HOST=
DOCKER_IMG=default
DOCKER_MAIN=True
"""


def _sandbox_root():
    """Per-xdist-worker sandbox root (reference mlcomp/__init__.py:10-13)."""
    worker = os.getenv('PYTEST_XDIST_WORKER')
    explicit = os.getenv('MLCOMP_TPU_ROOT')
    if explicit:
        return explicit
    base = os.path.expanduser('~/mlcomp_tpu')
    if worker is not None or os.getenv('MLCOMP_TPU_TEST') is not None:
        return os.path.join(
            os.path.expanduser('~/mlcomp_tpu_tests'), worker or 'main'
        )
    return base


ROOT_FOLDER = _sandbox_root()

# Wipe only auto-generated sandbox roots — never a user-supplied
# MLCOMP_TPU_ROOT, even when test env vars are also present.
if (os.getenv('PYTEST_XDIST_WORKER') is not None
        or os.getenv('MLCOMP_TPU_TEST') is not None) \
        and os.getenv('MLCOMP_TPU_ROOT') is None \
        and os.getenv('MLCOMP_TPU_KEEP_ROOT') is None:
    shutil.rmtree(ROOT_FOLDER, ignore_errors=True)

DATA_FOLDER = os.path.join(ROOT_FOLDER, 'data')
MODEL_FOLDER = os.path.join(ROOT_FOLDER, 'models')
TASK_FOLDER = os.path.join(ROOT_FOLDER, 'tasks')
LOG_FOLDER = os.path.join(ROOT_FOLDER, 'logs')
CONFIG_FOLDER = os.path.join(ROOT_FOLDER, 'configs')
DB_FOLDER = os.path.join(ROOT_FOLDER, 'db')
TMP_FOLDER = os.path.join(ROOT_FOLDER, 'tmp')

for _f in (DATA_FOLDER, MODEL_FOLDER, TASK_FOLDER, LOG_FOLDER,
           CONFIG_FOLDER, DB_FOLDER, TMP_FOLDER):
    os.makedirs(_f, exist_ok=True)

_ENV_FILE = os.path.join(CONFIG_FOLDER, '.env')
if not os.path.exists(_ENV_FILE):
    with open(_ENV_FILE, 'w') as _fh:
        _fh.write(_DEFAULT_ENV)


def _load_env(path):
    """Parse KEY=VALUE lines and export into os.environ.

    Values already present in the environment win (so the shell can
    override the config file), mirroring the reference's export behavior
    (mlcomp/__init__.py:44-57).
    """
    out = {}
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line or line.startswith('#') or '=' not in line:
                continue
            k, _, v = line.partition('=')
            k, v = k.strip(), v.strip()
            out[k] = os.environ.get(k, v)
            if out[k]:
                os.environ[k] = out[k]
    return out


_ENV = _load_env(_ENV_FILE)

TOKEN = _ENV.get('TOKEN', 'token')
# per-computer worker-class credential (issued by `server issue-token`);
# when set, RemoteSession authenticates with it instead of the
# full-control server TOKEN — see db/models/auth.py
# os.environ first: _ENV only reflects the environment for keys the
# MATERIALIZED .env file mentions — a machine whose configs/.env
# predates a key would silently ignore the exported variable
WORKER_TOKEN = os.environ.get('WORKER_TOKEN',
                              _ENV.get('WORKER_TOKEN', ''))
# opt-in pip install of DagLibrary-recorded versions at task download
# (reference worker/storage.py:206-215); default off — zero-egress
# images and pinned environments should not mutate themselves
INSTALL_LIBRARIES = os.environ.get(
    'INSTALL_LIBRARIES',
    _ENV.get('INSTALL_LIBRARIES', 'False')).lower() in ('1', 'true',
                                                        'yes')
DB_TYPE = _ENV.get('DB_TYPE', 'SQLITE')

if DB_TYPE == 'SQLITE':
    SA_CONNECTION_STRING = 'sqlite:///' + os.path.join(DB_FOLDER, 'sqlite.db')
elif DB_TYPE == 'SERVER':
    # multi-computer deployment: this machine proxies every DB statement
    # to the server host's /api/db (db/remote.py) — one durable store,
    # one open port, one secret
    SA_CONNECTION_STRING = _ENV.get(
        'SERVER_URL', f"http://{_ENV.get('IP', 'localhost')}:"
                      f"{_ENV.get('WEB_PORT', '4201')}")
else:  # POSTGRESQL — capability slot for a shared multi-host metadata store
    SA_CONNECTION_STRING = (
        f"postgresql://{_ENV.get('POSTGRES_USER')}:"
        f"{_ENV.get('POSTGRES_PASSWORD')}@{_ENV.get('POSTGRES_HOST')}:5432/"
        f"{_ENV.get('POSTGRES_DB')}"
    )

MASTER_PORT_RANGE = tuple(
    int(p) for p in _ENV.get('MASTER_PORT_RANGE', '29500-29510').split('-')
)
QUEUE_POLL_INTERVAL = float(_ENV.get('QUEUE_POLL_INTERVAL', '0.2'))
FILE_SYNC_INTERVAL = float(_ENV.get('FILE_SYNC_INTERVAL', '300'))
WORKER_USAGE_INTERVAL = float(_ENV.get('WORKER_USAGE_INTERVAL', '10'))
WEB_HOST = _ENV.get('WEB_HOST', '0.0.0.0')
WEB_PORT = int(_ENV.get('WEB_PORT', '4201'))
IP = _ENV.get('IP', 'localhost')
PORT = int(_ENV.get('PORT', '4202'))
SYNC_WITH_THIS_COMPUTER = _ENV.get(
    'SYNC_WITH_THIS_COMPUTER', 'True') == 'True'
CAN_PROCESS_TASKS = _ENV.get('CAN_PROCESS_TASKS', 'True') == 'True'
DOCKER_IMG = _ENV.get('DOCKER_IMG', 'default')
DOCKER_MAIN = _ENV.get('DOCKER_MAIN', 'True') == 'True'

# Persistent XLA compile cache, placed ONCE here, before anything
# imports jax (jax reads the variable when it is imported; children
# inherit it). A directory given from outside is left alone and nothing
# else in the tree sets one; otherwise every process of a checkout —
# each `run-task` subprocess is a fresh one — shares one fixed,
# git-ignored directory, because the path is part of the cache key and
# a directory that moves never hits. Forced-CPU runs (the test suite,
# CPU-pinned tasks) get no default: CPU compiles are cheap and the
# compile-event tests need them cold.
if 'JAX_COMPILATION_CACHE_DIR' not in os.environ \
        and os.environ.get('JAX_PLATFORMS') != 'cpu':
    os.environ['JAX_COMPILATION_CACHE_DIR'] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        '.jax_cache')

__all__ = [
    '__version__', 'ROOT_FOLDER', 'DATA_FOLDER', 'MODEL_FOLDER',
    'TASK_FOLDER', 'LOG_FOLDER', 'CONFIG_FOLDER', 'DB_FOLDER', 'TMP_FOLDER',
    'TOKEN', 'WORKER_TOKEN', 'INSTALL_LIBRARIES', 'DB_TYPE',
    'SA_CONNECTION_STRING', 'MASTER_PORT_RANGE',
    'QUEUE_POLL_INTERVAL', 'FILE_SYNC_INTERVAL', 'WORKER_USAGE_INTERVAL',
    'WEB_HOST', 'WEB_PORT', 'IP', 'PORT', 'SYNC_WITH_THIS_COMPUTER',
    'CAN_PROCESS_TASKS', 'DOCKER_IMG', 'DOCKER_MAIN',
]
