"""The harness: manifest, plug-in loading, spans, arithmetic, trace reduction."""
