import pytest

from distributed_deep_learning_tpu.utils.config import (
    Config, DistributedEnv, Mode, parse_args, parse_mesh_arg,
)


def test_reference_flags_parse():
    cfg = parse_args(["-l", "3", "-s", "64", "-e", "2", "-b", "128",
                      "-d", "cpu", "-w", "2", "-m", "pipeline", "-p", "16",
                      "-r", "4"], env={})
    assert cfg.num_layers == 3
    assert cfg.size == 64
    assert cfg.epochs == 2
    assert cfg.batch_size == 128
    assert cfg.device.value == "cpu"
    assert cfg.num_workers == 2
    assert cfg.mode is Mode.PIPELINE
    assert cfg.microbatch == 16
    assert cfg.world_size == 4


def test_defaults_match_reference():
    cfg = parse_args([], env={})
    assert cfg.mode is Mode.SEQUENTIAL
    assert cfg.seed == 42  # reference pins manual_seed(42)
    # reference getConfiguration defaults (CNN/main.py:51-57)
    assert cfg.epochs == 10
    assert cfg.batch_size == 32
    assert cfg.microbatch == 2
    assert cfg.world_size == 1
    assert not cfg.distributed.is_distributed


def test_workload_defaults():
    assert parse_args([], workload="cnn", env={}).num_layers == 2
    assert parse_args([], workload="cnn", env={}).size == 4
    assert parse_args([], workload="lstm", env={}).size == 128
    assert parse_args([], workload="mlp", env={}).size == 38


def test_mpi_env_detection():
    env = {"OMPI_COMM_WORLD_RANK": "3", "OMPI_COMM_WORLD_SIZE": "8",
           "OMPI_COMM_WORLD_LOCAL_RANK": "1", "MASTER_ADDR": "head-node"}
    dist = DistributedEnv.from_environ(env)
    assert dist.process_id == 3
    assert dist.num_processes == 8
    assert dist.local_process_id == 1
    assert dist.coordinator == "head-node:29500"
    assert dist.is_distributed


def test_explicit_env_beats_mpi():
    env = {"DDL_NUM_PROCESSES": "2", "DDL_PROCESS_ID": "1",
           "OMPI_COMM_WORLD_SIZE": "8", "OMPI_COMM_WORLD_RANK": "5"}
    dist = DistributedEnv.from_environ(env)
    assert dist.num_processes == 2
    assert dist.process_id == 1


def test_mesh_arg():
    assert parse_mesh_arg("data=4,stage=2") == {"data": 4, "stage": 2}
    assert parse_mesh_arg(None) is None
    assert parse_mesh_arg("") is None
    assert parse_mesh_arg("data=-1,model=2") == {"data": -1, "model": 2}


def test_mesh_arg_rejects_bad_strings():
    import pytest

    # a bad --mesh is a parse-time argparse-style error naming the known
    # axes, not a MeshSpec ValueError from deep inside startup
    with pytest.raises(SystemExit, match="known axes.*data.*fsdp"):
        parse_mesh_arg("batch=4")
    with pytest.raises(SystemExit, match="expected axis=N"):
        parse_mesh_arg("data")
    with pytest.raises(SystemExit, match="given twice"):
        parse_mesh_arg("data=2,data=4")
    with pytest.raises(SystemExit, match="must be an integer"):
        parse_mesh_arg("data=two")
    with pytest.raises(SystemExit, match="must be >= 1"):
        parse_mesh_arg("data=0")
    with pytest.raises(SystemExit, match="at most one axis may be -1"):
        parse_mesh_arg("data=-1,fsdp=-1")


def test_mesh_stage_nstages_conflict():
    import pytest

    with pytest.raises(SystemExit, match="conflicts with --nstages"):
        parse_args(["--mesh", "stage=4", "--nstages", "2"], workload="mlp")
    # agreeing values are fine
    c = parse_args(["--mesh", "stage=2", "--nstages", "2"], workload="mlp")
    assert c.mesh_shape == {"stage": 2}


def test_autotune_plan_flags():
    import pytest

    c = parse_args(["--autotune"], workload="mlp")
    assert c.autotune and c.plan_file is None
    # --plan with --autotune is the OUTPUT path; it need not exist yet
    c = parse_args(["--autotune", "--plan", "/tmp/_no_such.plan.json"],
                   workload="mlp")
    assert c.autotune and c.plan_file == "/tmp/_no_such.plan.json"
    # --plan alone replays an artifact: a missing file fails at parse time
    with pytest.raises(SystemExit, match="no such file"):
        parse_args(["--plan", "/tmp/_no_such.plan.json"], workload="mlp")


def test_config_immutable_replace():
    cfg = Config()
    cfg2 = cfg.replace(epochs=9)
    assert cfg.epochs != 9 and cfg2.epochs == 9


# --- nothing hides the device (-d, --spawn, the devices line) ---------------

def test_device_flag_unset_means_default_backend():
    from distributed_deep_learning_tpu.utils.config import Device

    assert parse_args([], env={}).device is None
    assert Config().device is None
    assert parse_args(["-d", "tpu"], env={}).device is Device.TPU


@pytest.mark.parametrize("flag", ["tpu", "gpu"])
def test_explicit_tpu_without_a_tpu_is_an_error(flag):
    """`-d tpu` on a box whose default backend is the CPU used to train on
    the CPU, print the same log grammar and exit 0."""
    from distributed_deep_learning_tpu.workloads import base

    with pytest.raises(ValueError, match="default backend is 'cpu'"):
        base._devices(parse_args(["-d", flag], env={}))
    assert base._devices(parse_args([], env={}))[0].platform == "cpu"
    assert base._devices(parse_args(["-d", "cpu"], env={}))[0].platform \
        == "cpu"


def test_spawn_refuses_an_explicit_tpu():
    """--spawn forces its ranks onto the CPU whatever -d says: with -d tpu
    it refuses rather than pretending (nothing is launched)."""
    from distributed_deep_learning_tpu.__main__ import main

    with pytest.raises(SystemExit, match="--spawn runs its ranks on the CPU"):
        main(["mlp", "-r", "2", "-m", "data", "-d", "tpu", "--spawn"])


def test_run_logs_platform_kind_and_count(capsys):
    import jax

    from distributed_deep_learning_tpu.__main__ import main

    _, history = main(["mlp", "-e", "1", "-b", "64"])
    assert history
    assert (f"\"devices: platform=cpu device_kind='cpu' "
            f"count={len(jax.devices())}\"") in capsys.readouterr().out


def test_serve_under_a_staged_mode_is_rejected_before_training():
    """An explicit --serve that cannot serve raises — it used to train to
    the end and then log "serve skipped"."""
    from distributed_deep_learning_tpu.workloads import northstar

    cfg = parse_args(["-m", "pipeline", "--serve"], workload="gpt", env={})
    with pytest.raises(ValueError, match="--serve needs the whole-model"):
        northstar._gpt_pre_check(cfg, dataset=None)


def test_require_devices_names_the_rehearsal_recipe():
    import jax

    from distributed_deep_learning_tpu.runtime.bootstrap import (
        describe_devices, require_devices)

    n = len(jax.devices())
    assert len(require_devices(n)) == n
    with pytest.raises(SystemExit, match=(
            f"JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n + 1}")):
        require_devices(n + 1)
    assert describe_devices() == {"platform": "cpu", "device_kind": "cpu",
                                  "device_count": n}
