"""risingwave_tpu — a TPU-native streaming-dataflow framework.

A from-scratch re-design of RisingWave's streaming engine (reference:
/root/reference, Rust) for TPU hardware: SQL-defined incrementally-maintained
materialized views over unbounded streams, with

- changelog chunk processing (Insert/Delete/UpdateDelete/UpdateInsert ops)
  on fixed-capacity columnar device chunks with visibility masks,
- epoch-aligned barrier checkpoints (Chandy-Lamport), exactly-once state
  commit to an LSM state store,
- consistent-hash (vnode) partitioned operator state held in HBM as
  jax-sharded arrays over a device mesh, shuffles as XLA collectives,
- a jax.jit-lowered vectorized expression engine.

Layer map (mirrors SURVEY.md §1 of the reference):
  frontend/   SQL -> bound plan -> stream fragment graph
  meta/       barrier manager, catalog, cluster, recovery
  stream/     executors (source, project, filter, hash_agg, sorted_join,
              hop_window, retract_top_n, materialize, dispatch/merge), actors
  expr/       expression IR + vectorized jnp evaluation + aggregates
  state/      StateTable facade, memory & LSM (hummock-lite) state stores
  parallel/   vnode<->mesh mapping, all_to_all exchange
  ops/        device kernels: hashing, open-addressing tables, segments
  common/     chunk/type/row/vnode/epoch data kernel
  connectors/ sources (nexmark, datagen) and sinks
"""

import jax

# The reference's type system is 64-bit first (Int64 ids, Timestamp micros,
# Epoch = ms<<16; src/common/src/types/mod.rs:110). Enable x64 once, at
# import, before any tracing happens.
jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
