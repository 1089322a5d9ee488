"""Erasure-code plugin registry.

Reference seam: ErasureCodePluginRegistry
(/root/reference/src/erasure-code/ErasureCodePlugin.h:45-79, .cc:86-196): a
singleton that dlopens `libec_<name>.so`, checks the plugin's version against
the build, calls its factory, and asserts the plugin echoes the profile back.

Here plugins are Python classes (optionally backed by native code or Pallas
kernels) registered by name.  Dynamic loading maps to `importlib` of
`ceph_tpu_ec_<name>` modules exposing `__erasure_code_init__(registry)` and
`__erasure_code_version__` — the same three-point contract (entry point,
version check, registration) so the reference's negative-path tests
(missing entry point, version mismatch, fail-to-register) carry over
(/root/reference/src/test/erasure-code/TestErasureCodePlugin.cc).
"""

from __future__ import annotations

import importlib
import threading
from typing import Callable, Dict, Optional

import ceph_tpu
from ceph_tpu.ec.interface import ErasureCode, ErasureCodeError, ErasureCodeProfile

PLUGIN_VERSION = ceph_tpu.__version__
_MODULE_PREFIX = "ceph_tpu_ec_"  # the `libec_` analog for importable plugins

Factory = Callable[[ErasureCodeProfile], ErasureCode]


class ErasureCodePlugin:
    """A named factory with a version stamp."""

    def __init__(self, name: str, factory: Factory,
                 version: str = PLUGIN_VERSION):
        self.name = name
        self.factory = factory
        self.version = version


class ErasureCodePluginRegistry:
    _instance: Optional["ErasureCodePluginRegistry"] = None
    _instance_lock = threading.Lock()

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._plugins: Dict[str, ErasureCodePlugin] = {}
        self.disable_dlclose = False

    @classmethod
    def instance(cls) -> "ErasureCodePluginRegistry":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
                _register_builtin(cls._instance)
            return cls._instance

    def add(self, name: str, plugin: ErasureCodePlugin) -> int:
        with self._lock:
            if name in self._plugins:
                return -17  # EEXIST, same as the reference
            self._plugins[name] = plugin
            return 0

    def get(self, name: str) -> Optional[ErasureCodePlugin]:
        with self._lock:
            return self._plugins.get(name)

    def remove(self, name: str) -> int:
        with self._lock:
            return 0 if self._plugins.pop(name, None) else -2

    def load(self, name: str) -> ErasureCodePlugin:
        """Dynamic load of `ceph_tpu_ec_<name>` (dlopen analog).

        EXDEV on version mismatch, ENOENT on missing module, ENOEXEC on a
        module without the init entry point — the reference's error map
        (ErasureCodePlugin.cc:120-178).
        """
        plugin = self.get(name)
        if plugin is not None:
            return plugin
        try:
            mod = importlib.import_module(_MODULE_PREFIX + name)
        except ImportError as e:
            raise ErasureCodeError(2, f"load dlopen({name}): {e}")
        version = getattr(mod, "__erasure_code_version__", None)
        if version is None:
            raise ErasureCodeError(8, f"{name} has no version entry point")
        if version != PLUGIN_VERSION:
            raise ErasureCodeError(
                18, f"{name} version {version} != expected {PLUGIN_VERSION}")
        init = getattr(mod, "__erasure_code_init__", None)
        if init is None:
            raise ErasureCodeError(8, f"{name} has no init entry point")
        ret = init(self)
        if ret not in (0, None):
            raise ErasureCodeError(-ret if isinstance(ret, int) else 5,
                                   f"{name} init failed")
        plugin = self.get(name)
        if plugin is None:
            raise ErasureCodeError(6, f"{name} init did not register itself")
        return plugin

    def preload(self, plugins_csv: str) -> None:
        """Preload a comma-separated plugin list (osd_erasure_code_plugins;
        global_init_preload_erasure_code, global_init.cc:587-620)."""
        for name in filter(None, (p.strip() for p in plugins_csv.split(","))):
            self.load(name)

    def factory(self, plugin_name: str, profile: ErasureCodeProfile,
                ) -> ErasureCode:
        plugin = self.get(plugin_name) or self.load(plugin_name)
        codec = plugin.factory(profile)
        # The reference asserts the codec echoes the profile back
        # (ErasureCodePlugin.cc:104-112).
        prof = codec.get_profile()
        for key, val in profile.items():
            assert prof.get(key) == val, f"plugin dropped profile key {key}"
        return codec

    def names(self):
        with self._lock:
            return sorted(self._plugins)


def _make_jax_factory(technique: str) -> Factory:
    def factory(profile: ErasureCodeProfile) -> ErasureCode:
        from ceph_tpu.ec.bitmatrix_plugin import ErasureCodeJaxBitmatrix
        from ceph_tpu.ec.jax_plugin import ErasureCodeJax

        tech = profile.get("technique", technique)
        if tech in ErasureCodeJaxBitmatrix.TECHNIQUES:
            codec: ErasureCode = ErasureCodeJaxBitmatrix(technique=tech)
        else:
            codec = ErasureCodeJax(technique=tech)
        codec.init(profile)
        return codec

    return factory


def _register_builtin(reg: ErasureCodePluginRegistry) -> None:
    # `ec_jax` is the flagship plugin; `jerasure` and `isa` are registered as
    # compatibility aliases so reference profiles
    # (plugin=jerasure technique=reed_sol_van k=2 m=2 — the
    # osd_pool_default_erasure_code_profile) resolve to the TPU codec.
    # `isa` thereby takes jerasure's reed_sol_van matrix; Ceph's ISA
    # plugin builds its own with ISA-L's gf_gen_rs_matrix, so an isa
    # pool's parity rows after the first are not ISA-L's (an open
    # question).
    for name in ("ec_jax", "jerasure", "isa"):
        reg.add(name, ErasureCodePlugin(name, _make_jax_factory("reed_sol_van")))

    def lrc_factory(profile: ErasureCodeProfile) -> ErasureCode:
        from ceph_tpu.ec.lrc import ErasureCodeLrc

        codec = ErasureCodeLrc()
        codec.init(profile)
        return codec

    def shec_factory(profile: ErasureCodeProfile) -> ErasureCode:
        from ceph_tpu.ec.shec import ErasureCodeShec

        codec = ErasureCodeShec(
            technique=profile.setdefault("technique", "multiple"))
        codec.init(profile)
        return codec

    def clay_factory(profile: ErasureCodeProfile) -> ErasureCode:
        from ceph_tpu.ec.clay import ErasureCodeClay

        codec = ErasureCodeClay()
        codec.init(profile)
        return codec

    def msr_factory(profile: ErasureCodeProfile) -> ErasureCode:
        from ceph_tpu.ec.msr import ErasureCodeMsr

        codec = ErasureCodeMsr()
        codec.init(profile)
        return codec

    reg.add("lrc", ErasureCodePlugin("lrc", lrc_factory))
    reg.add("shec", ErasureCodePlugin("shec", shec_factory))
    reg.add("clay", ErasureCodePlugin("clay", clay_factory))
    reg.add("ec_msr", ErasureCodePlugin("ec_msr", msr_factory))


def create_erasure_code(profile: ErasureCodeProfile) -> ErasureCode:
    """Build a codec from a reference-style profile string map."""
    plugin = profile.get("plugin", "ec_jax")
    return ErasureCodePluginRegistry.instance().factory(plugin, dict(profile))
