"""Language models served by the generate engine (engine/generate.py).

A second model family beside the IR importer and the vision zoo: plain
JAX, weights made on the device from a seed, installed by
``fetch-models --synthesize-lm`` as a config file and found by
``ModelRegistry.lm_config``. Nothing here is imported by a server that
serves no ``describe`` stage.
"""
