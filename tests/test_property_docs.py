"""Property-docs lint: registering a knob without documenting it fails.

Every SESSION property (metadata.SESSION_PROPERTY_DEFAULTS) and every
server/fleet constructor property must carry a docs entry in
SESSION_PROPERTY_DOCS / SERVER_PROPERTY_DOCS — those dicts feed SHOW
SESSION and system.runtime.server_properties, so a missing entry is an
operator-invisible knob. The session check is bidirectional: a doc for
a property that no longer exists is stale and fails too.
"""

import inspect

from trino_tpu.metadata import (SERVER_PROPERTY_DOCS,
                                SESSION_PROPERTY_DEFAULTS,
                                SESSION_PROPERTY_DOCS)

# constructor parameters that inject collaborators rather than
# configure behavior — not operator-facing properties. `requires` names
# what a deployment depends on (server/app.CAPABILITIES): the
# constructor's check reads it and nothing else does
_WIRING = {
    "self", "runner", "resource_groups", "result_cache", "scan_cache",
    "table_cache", "warmup_manifest", "worker_env", "engine_env",
    "engine_kwargs", "requires",
}


def test_every_session_property_documented():
    missing = set(SESSION_PROPERTY_DEFAULTS) - set(SESSION_PROPERTY_DOCS)
    assert not missing, \
        f"session properties without docs: {sorted(missing)}"


def test_no_stale_session_property_docs():
    stale = set(SESSION_PROPERTY_DOCS) - set(SESSION_PROPERTY_DEFAULTS)
    assert not stale, \
        f"docs for unregistered session properties: {sorted(stale)}"


def test_session_docs_are_substantive():
    for name, doc in SESSION_PROPERTY_DOCS.items():
        assert isinstance(doc, str) and len(doc.strip()) >= 20, \
            f"doc for {name!r} is empty or too thin"


def test_every_server_property_documented():
    from trino_tpu.fleet.server import FleetServer
    from trino_tpu.server.app import TrinoServer
    params = set()
    for ctor in (TrinoServer.__init__, FleetServer.__init__):
        params |= set(inspect.signature(ctor).parameters)
    missing = (params - _WIRING) - set(SERVER_PROPERTY_DOCS)
    assert not missing, \
        f"server properties without docs: {sorted(missing)}"


def test_server_docs_are_substantive():
    for name, doc in SERVER_PROPERTY_DOCS.items():
        assert isinstance(doc, str) and len(doc.strip()) >= 20, \
            f"doc for {name!r} is empty or too thin"
