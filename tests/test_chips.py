"""One process per chip (launch/chips.py): the supervisors' placement of
``out=tpu`` workers on a TPU host — distinct chips, everyone else pinned
to the CPU, over-subscription refused before anything is spawned."""
import pytest

from dynamo_tpu.launch import serve
from dynamo_tpu.launch.chips import ChipPlacement, chips_needed

TPU_WORKER = ["out=tpu", "--model-config", "tiny"]


def test_chips_needed_reads_engine_and_tp():
    assert chips_needed(["out=mocker", "--model-name", "m"]) == 0
    assert chips_needed(TPU_WORKER) == 1
    assert chips_needed(TPU_WORKER + ["--tensor-parallel-size", "4"]) == 4
    assert chips_needed(TPU_WORKER + ["--tensor-parallel-size=2"]) == 2


def test_one_chip_per_worker_then_refusal():
    chips = ChipPlacement(total=4)
    for i in range(4):
        env, taken = chips.env_for(TPU_WORKER)
        assert taken == [i] and env == {
            "TPU_VISIBLE_CHIPS": str(i),
            # without both bounds the second process dies on libtpu's
            # multi-process lockfile (measured on the 2x2 v5e host)
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
    with pytest.raises(ValueError, match="one process per chip"):
        chips.env_for(TPU_WORKER)
    chips.release([2])
    assert chips.env_for(TPU_WORKER)[1] == [2]


def test_whole_host_worker_and_unsupported_split():
    chips = ChipPlacement(total=4)
    env, taken = chips.env_for(TPU_WORKER + ["--tensor-parallel-size", "4"])
    assert env == {} and taken == [0, 1, 2, 3]
    with pytest.raises(ValueError, match="one process per chip"):
        chips.env_for(TPU_WORKER)
    with pytest.raises(ValueError, match="whole host"):
        ChipPlacement(total=4).env_for(
            TPU_WORKER + ["--tensor-parallel-size", "2"])


def test_chipless_children_are_pinned_to_cpu_only_on_tpu_hosts():
    assert ChipPlacement(total=4).env_for(["out=mocker"]) == (
        {"JAX_PLATFORMS": "cpu"}, [])
    # no TPU on the host (this box, CI): nothing to place or protect
    assert ChipPlacement(total=0).env_for(TPU_WORKER) == ({}, [])
    assert ChipPlacement(total=0).env_for(["out=mocker"]) == ({}, [])


def test_supervisor_places_replicas_or_refuses(monkeypatch):
    monkeypatch.setattr(serve, "ChipPlacement",
                        lambda: ChipPlacement(total=4))

    def graph(replicas):
        return {"frontend": {}, "workers": [
            {"name": "w", "replicas": replicas, "args": TPU_WORKER}]}

    sup = serve.Supervisor(graph(4))
    sup._build_children()
    by_name = {c.name: c.env for c in sup.children}
    assert [by_name[f"w-{i}"]["TPU_VISIBLE_CHIPS"] for i in range(4)] == [
        "0", "1", "2", "3"]
    assert by_name["frontend"] == {"JAX_PLATFORMS": "cpu"}
    assert by_name["control-plane"] == {"JAX_PLATFORMS": "cpu"}
    with pytest.raises(SystemExit, match="w-4.*one process per chip"):
        serve.Supervisor(graph(5))._build_children()
