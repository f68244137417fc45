"""Resource specification: the cluster description the user hands to AutoDist.

Capability parity with reference ``autodist/resource_spec.py:45-331``:

- YAML schema ``nodes:`` (address / chief / accelerators / cpus / ssh_config /
  network_bandwidth, bandwidth defaulting to 1 GBE as in reference ``:209-215``) and
  ``ssh:`` config groups (username / key_file / port / python_venv / shared_envs,
  reference ``:291-331``).
- ``DeviceSpec`` with the string form ``address:TYPE:index`` (reference ``:241-265``
  used ``ip:GPU:0``); here TPU is a first-class device type.
- Chief rules: exactly one chief; a single-node spec is implicitly chief (reference
  ``:100-138`` via cluster, surfaced here).

TPU-native extension: a node may declare ``tpus: <count>`` and the spec may carry a
``mesh:`` section naming logical axis sizes (``data`` / ``reduce`` / ``model`` / ``seq`` /
``expert`` / ``pipe``). The mesh section is consumed by
:func:`autodist_tpu.parallel.mesh.build_mesh`.
"""

import copy
import enum
import os
from typing import Dict, List, Optional, Tuple

import yaml

# Default bandwidth in Gbps when a node does not declare one — reference
# resource_spec.py:209-215 defaults to 1 GBE.
DEFAULT_NETWORK_BANDWIDTH_GBPS = 1


class DeviceType(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2


class Connectivity(enum.Enum):
    """Relative closeness of two devices (reference resource_spec.py Connectivity)."""

    ETHERNET = 0     # cross-host over DCN/ethernet
    SAME_HOST = 1    # same host, different chips (PCIe on GPU; ICI on TPU slice)
    SAME_DEVICE = 2


class DeviceSpec:
    """One physical device, addressable as ``host:TYPE:index``.

    Reference parity: ``resource_spec.py:241-265`` (``ip:GPU:0`` string round-trip,
    tested by reference ``tests/test_device_spec.py:11-20``).
    """

    def __init__(self, host: str, device_type: DeviceType = DeviceType.CPU,
                 device_index: int = 0):
        self.host = host
        self.device_type = device_type
        self.device_index = device_index

    @property
    def name_string(self) -> str:
        if self.device_type is DeviceType.CPU:
            return self.host
        return f"{self.host}:{self.device_type.name}:{self.device_index}"

    @classmethod
    def from_string(cls, name: str) -> "DeviceSpec":
        parts = name.split(":")
        if len(parts) == 1:
            return cls(parts[0], DeviceType.CPU, 0)
        if len(parts) == 3:
            return cls(parts[0], DeviceType[parts[1].upper()], int(parts[2]))
        raise ValueError(f"Malformed device string: {name!r}")

    def connectivity_with(self, other: "DeviceSpec") -> Connectivity:
        if self.host != other.host:
            return Connectivity.ETHERNET
        if (self.device_type, self.device_index) == (other.device_type, other.device_index):
            return Connectivity.SAME_DEVICE
        return Connectivity.SAME_HOST

    def __repr__(self):
        return f"DeviceSpec({self.name_string})"

    def __eq__(self, other):
        return isinstance(other, DeviceSpec) and self.name_string == other.name_string

    def __hash__(self):
        return hash(self.name_string)


class SSHConfig:
    """One ssh group entry (reference resource_spec.py:280-306)."""

    def __init__(self, name: str, conf: dict):
        self.name = name
        self.username = conf.get("username", "")
        self.port = int(conf.get("port", 22))
        self.python_venv = conf.get("python_venv", "")
        self.key_file = conf.get("key_file", "")
        self.shared_envs = dict(conf.get("shared_envs", {}))


class SSHConfigMap(dict):
    """name -> SSHConfig (reference resource_spec.py:309-331)."""

    def __init__(self, conf: Optional[dict] = None):
        super().__init__()
        for name, c in (conf or {}).items():
            self[name] = SSHConfig(name, c)


class Node:
    """One host entry from the ``nodes:`` list."""

    def __init__(self, entry: dict):
        if "address" not in entry:
            raise ValueError("Every node needs an 'address'")
        self.address: str = str(entry["address"])
        self.chief: bool = bool(entry.get("chief", False))
        self.ssh_config_name: Optional[str] = entry.get("ssh_config")
        # Whether the spec stated a bandwidth (vs the 1 GBE default): consumers
        # making numerics-affecting choices (AutoStrategy's lossy wire codecs)
        # must not treat the defaulted value as a measurement.
        self.bandwidth_specified: bool = "network_bandwidth" in entry
        self.network_bandwidth: int = int(
            entry.get("network_bandwidth", DEFAULT_NETWORK_BANDWIDTH_GBPS))
        if self.network_bandwidth <= 0:
            raise ValueError(f"network_bandwidth must be positive on node {self.address}")
        # Accelerators. `tpus: N` is the TPU-native form; `gpus: [i,...]` is accepted for
        # schema compat with reference specs and treated as generic accelerator indices.
        self.tpu_indices: List[int] = list(range(int(entry.get("tpus", 0))))
        self.gpu_indices: List[int] = [int(i) for i in entry.get("gpus", [])]
        self.cpu_indices: List[int] = [int(i) for i in entry.get("cpus", [])] or [0]

    @property
    def accelerator_devices(self) -> List[DeviceSpec]:
        devs = [DeviceSpec(self.address, DeviceType.TPU, i) for i in self.tpu_indices]
        devs += [DeviceSpec(self.address, DeviceType.GPU, i) for i in self.gpu_indices]
        return devs

    @property
    def cpu_devices(self) -> List[DeviceSpec]:
        return [DeviceSpec(self.address, DeviceType.CPU, i) for i in self.cpu_indices]


class ResourceSpec:
    """Parsed resource spec.

    Accepts a YAML file path, a YAML string, or a pre-parsed dict. With no argument,
    builds a single-host spec from the locally visible JAX device count (the
    "fake-cluster"/single-node mode used by tests; reference single-node specs are
    ``tests/integration/resource_specs/r0.yml``).
    """

    def __init__(self, resource_file: Optional[str] = None, *, resource_info: Optional[dict] = None):
        if resource_info is not None:
            info = copy.deepcopy(resource_info)
        elif resource_file is None:
            info = self._local_default_info()
        elif os.path.exists(resource_file):
            with open(resource_file) as f:
                info = yaml.safe_load(f) or {}
        else:
            # Allow passing inline YAML text.
            info = yaml.safe_load(resource_file)
            if not isinstance(info, dict):
                raise FileNotFoundError(f"No such resource spec file: {resource_file}")

        if not isinstance(info, dict):
            raise ValueError(f"Resource spec must be a YAML mapping, got {type(info).__name__}")
        nodes_conf = info.get("nodes") or []
        if not nodes_conf:
            raise ValueError("Resource spec has no nodes")
        self.nodes: List[Node] = [Node(e) for e in nodes_conf]
        self.ssh_config_map = SSHConfigMap(info.get("ssh"))
        self.mesh_config: Dict[str, int] = dict(info.get("mesh", {}) or {})

        self._validate_and_set_chief()

    @staticmethod
    def _local_default_info() -> dict:
        import jax
        # Whatever the local platform (TPU or the CPU test mesh), the visible
        # devices are this spec's accelerators, declared under the `tpus:` key.
        n = len(jax.devices())
        return {"nodes": [{"address": "localhost", "tpus": n, "chief": True}]}

    def _validate_and_set_chief(self):
        addresses = [n.address for n in self.nodes]
        if len(set(addresses)) != len(addresses):
            raise ValueError("Duplicate node addresses in resource spec")
        chiefs = [n for n in self.nodes if n.chief]
        if len(self.nodes) == 1 and not chiefs:
            self.nodes[0].chief = True
            chiefs = [self.nodes[0]]
        if len(chiefs) != 1:
            raise ValueError(
                f"Exactly one chief required, found {len(chiefs)} "
                f"(reference requires the same: one chief node)")
        self._chief = chiefs[0]
        for n in self.nodes:
            if n.ssh_config_name is not None and n.ssh_config_name not in self.ssh_config_map:
                raise ValueError(
                    f"Node {n.address} references unknown ssh_config "
                    f"{n.ssh_config_name!r}; defined groups: {sorted(self.ssh_config_map)}")

    # --- accessors (reference resource_spec.py:80-158 property surface) ---

    @property
    def chief_address(self) -> str:
        return self._chief.address

    @property
    def node_addresses(self) -> List[str]:
        return [n.address for n in self.nodes]

    # Sorted iteration is load-bearing for deterministic port/process-index assignment —
    # every host must derive the same ordering independently (reference cluster.py:70-82).
    @property
    def sorted_nodes(self) -> List[Node]:
        return sorted(self.nodes, key=lambda n: (not n.chief, n.address))

    @property
    def accelerator_devices(self) -> List[Tuple[str, DeviceSpec]]:
        out = []
        for node in self.sorted_nodes:
            for dev in node.accelerator_devices:
                out.append((dev.name_string, dev))
        return out

    @property
    def tpu_devices(self) -> List[Tuple[str, DeviceSpec]]:
        return [(s, d) for s, d in self.accelerator_devices if d.device_type is DeviceType.TPU]

    @property
    def gpu_devices(self) -> List[Tuple[str, DeviceSpec]]:
        return [(s, d) for s, d in self.accelerator_devices if d.device_type is DeviceType.GPU]

    @property
    def cpu_devices(self) -> List[Tuple[str, DeviceSpec]]:
        out = []
        for node in self.sorted_nodes:
            for dev in node.cpu_devices:
                out.append((dev.name_string, dev))
        return out

    @property
    def num_accelerators(self) -> int:
        return len(self.accelerator_devices)

    @property
    def num_nodes(self) -> int:
        return len(self.nodes)

    def node_bandwidth(self, address: str) -> int:
        for n in self.nodes:
            if n.address == address:
                return n.network_bandwidth
        raise KeyError(address)

    def ssh_config_for(self, address: str) -> Optional[SSHConfig]:
        for n in self.nodes:
            if n.address == address:
                if n.ssh_config_name is None:
                    return None
                return self.ssh_config_map[n.ssh_config_name]
        raise KeyError(address)

    # Replica devices: the devices that carry data-parallel replicas. Reference strategy
    # builders use "all GPUs, plus the CPU of GPU-less nodes" (ps_strategy.py:37-56).
    @property
    def replica_devices(self) -> List[DeviceSpec]:
        out: List[DeviceSpec] = []
        for node in self.sorted_nodes:
            accs = node.accelerator_devices
            if accs:
                out.extend(accs)
            else:
                out.append(node.cpu_devices[0])
        return out

    def __repr__(self):
        return (f"ResourceSpec(nodes={self.node_addresses}, chief={self.chief_address}, "
                f"accelerators={self.num_accelerators})")
