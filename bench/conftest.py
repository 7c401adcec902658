"""Every bench test leaves the engine's module state as it found it."""
import pytest


@pytest.fixture(autouse=True)
def _engine_state():
    from repro.serving import engine as engine_mod
    from repro.serving.kv_cache import SlotCache
    params, admit = engine_mod.image_params, SlotCache.admit
    yield
    engine_mod.image_params, SlotCache.admit = params, admit
    engine_mod._IMAGE_CACHE.clear()
