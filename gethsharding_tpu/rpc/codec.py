"""Wire encoding for the mainchain RPC surface.

JSON-RPC 2.0 payload values: addresses/hashes/byte strings as 0x-hex,
bn256 curve points as hex-int coordinate arrays (G1 = [x, y], G2 =
[[xa, xb], [ya, yb]], null = infinity/absent), registry entries and
collation records as plain objects. Deliberately schema-first and
version-tagged so a non-Python peer can implement the same surface.

A ROW of points on the committee plane (`shard_verifyCommittees`'
`sig_rows` and `pk_rows`) has two wire forms, and a handler accepts
both, row by row, telling them apart by the row's JSON type:

- a STRING: the packed row, 0x-hex of the points' coordinates back to
  back, each coordinate 32 bytes big-endian. G1 is 64 bytes a point,
  ``x ‖ y``; G2 is 128 bytes a point, ``xa ‖ xb ‖ ya ‖ yb`` (of
  ``x = xa + xb·i``: real part first, the order of the list form).
  ``"0x"`` is the empty row. The packed form has no absent point. A
  string whose byte length is no multiple of the point size (or no
  hex) gets the error response a malformed list gets.
- a LIST: the points in the coordinate-array form above, where a slot
  may be null. A sender uses it for a row with an absent point or a
  coordinate that does not fit 32 bytes; older clients send only it.

One request may mix the two. Coordinates are not reduced on the wire:
the verifier reduces them mod p, in either form.
"""

from __future__ import annotations

from typing import Optional

from gethsharding_tpu.crypto import bn256
from gethsharding_tpu.crypto.pointrows import (
    G1_POINT_BYTES, G2_POINT_BYTES, PackedRow, pack_row)
from gethsharding_tpu.utils.hexbytes import Address20, Hash32


def enc_bytes(b: bytes) -> str:
    return "0x" + bytes(b).hex()


def dec_bytes(s: str) -> bytes:
    return bytes.fromhex(s[2:] if s.startswith("0x") else s)


def enc_g1(p: Optional[bn256.G1Point]) -> Optional[list]:
    return None if p is None else [hex(p[0]), hex(p[1])]


def dec_g1(v) -> Optional[bn256.G1Point]:
    return None if v is None else (int(v[0], 16), int(v[1], 16))


def enc_g2(p: Optional[bn256.G2Point]) -> Optional[list]:
    if p is None:
        return None
    x, y = p
    return [[hex(x.a), hex(x.b)], [hex(y.a), hex(y.b)]]


def dec_g2(v) -> Optional[bn256.G2Point]:
    if v is None:
        return None
    (xa, xb), (ya, yb) = v
    return (bn256.Fp2(int(xa, 16), int(xb, 16)),
            bn256.Fp2(int(ya, 16), int(yb, 16)))


def enc_registry(entry) -> Optional[dict]:
    if entry is None:
        return None
    return {
        "deregisteredPeriod": entry.deregistered_period,
        "poolIndex": entry.pool_index,
        "balance": entry.balance,
        "deposited": entry.deposited,
        "blsPubkey": enc_g2(entry.bls_pubkey),
        "blsPop": enc_g1(entry.bls_pop),
    }


def dec_registry(obj: Optional[dict]):
    if obj is None:
        return None
    from gethsharding_tpu.smc.state_machine import Notary

    return Notary(
        deregistered_period=obj["deregisteredPeriod"],
        pool_index=obj["poolIndex"],
        balance=obj["balance"],
        deposited=obj["deposited"],
        bls_pubkey=dec_g2(obj["blsPubkey"]),
        bls_pop=dec_g1(obj["blsPop"]),
    )


def enc_record(record) -> Optional[dict]:
    if record is None:
        return None
    return {
        "chunkRoot": enc_bytes(record.chunk_root),
        "proposer": enc_bytes(record.proposer),
        "isElected": record.is_elected,
        "signature": enc_bytes(record.signature),
        "voteSigs": {str(i): [enc_g1(v.sig), enc_bytes(v.signer)]
                     for i, v in record.vote_sigs.items()},
        "voteCount": record.vote_count,
    }


def dec_record(obj: Optional[dict]):
    if obj is None:
        return None
    from gethsharding_tpu.smc.state_machine import CollationRecord, VoteSig

    return CollationRecord(
        chunk_root=Hash32(dec_bytes(obj["chunkRoot"])),
        proposer=Address20(dec_bytes(obj["proposer"])),
        is_elected=obj["isElected"],
        signature=dec_bytes(obj["signature"]),
        vote_sigs={int(i): VoteSig(sig=dec_g1(v[0]),
                                   signer=Address20(dec_bytes(v[1])))
                   for i, v in obj["voteSigs"].items()},
        vote_count=obj["voteCount"],
    )


# -- verification plane codecs (the serving RPC surface) -------------------
# The committee and DAS planes ship RAGGED batches: per-row committee
# signature/pubkey point lists and per-row merkle sibling paths. The
# wire forms are plain nested JSON of the scalar codecs above, so a
# frontend router can balance EVERY SigBackend op cross-process with
# the same schema-first contract as the rest of the surface.


def _enc_point_rows(rows, point_size: int, enc_point) -> list:
    out = []
    for row in rows:
        packed = pack_row(row, point_size)
        out.append([enc_point(p) for p in row] if packed is None
                   else enc_bytes(packed.raw))
    return out


def _dec_point_rows(rows, point_size: int, dec_point) -> list:
    # a string is a packed row, a list the points one by one: the two
    # wire forms of a row (module docstring); `bytes.fromhex` and
    # `PackedRow` raise ValueError on a malformed string, which the
    # server answers with an error, like a malformed list
    return [PackedRow(dec_bytes(row), point_size) if isinstance(row, str)
            else [dec_point(p) for p in row] for row in rows]


def enc_g1_rows(rows) -> list:
    """Per-row G1 point lists (committee vote signatures): a row as one
    packed string, or as a list of points where it holds a `None`."""
    return _enc_point_rows(rows, G1_POINT_BYTES, enc_g1)


def dec_g1_rows(rows) -> list:
    return _dec_point_rows(rows, G1_POINT_BYTES, dec_g1)


def enc_g2_rows(rows) -> list:
    """Per-row G2 point lists (committee member pubkeys), as
    `enc_g1_rows`."""
    return _enc_point_rows(rows, G2_POINT_BYTES, enc_g2)


def dec_g2_rows(rows) -> list:
    return _dec_point_rows(rows, G2_POINT_BYTES, dec_g2)


def dec_committee_call(messages, sig_rows, pk_rows, pk_row_keys) -> tuple:
    """The `shard_verifyCommittees` argument plane, for the replica's
    handler and the frontend's alike: (messages, sig_rows, pk_rows,
    keys, packed), `packed` the number of rows whose signature AND key
    row arrived packed."""
    sigs, pks = dec_g1_rows(sig_rows), dec_g2_rows(pk_rows)
    keys = None if pk_row_keys is None else [
        None if k is None else str(k) for k in pk_row_keys]
    packed = sum(1 for s, p in zip(sigs, pks)
                 if isinstance(s, PackedRow) and isinstance(p, PackedRow))
    return [dec_bytes(m) for m in messages], sigs, pks, keys, packed


def enc_pk_row_keys(keys) -> Optional[list]:
    """Optional per-row pk-plane cache keys. Keys are arbitrary
    hashables caller-side (the notary uses int tuples); the wire form
    is their `repr` — injective for the int/str/tuple keys in use, so
    the remote backend's cache key still uniquely determines the row's
    points, and stable across processes (unlike `hash`, repr does not
    depend on PYTHONHASHSEED)."""
    if keys is None:
        return None
    return [None if k is None else repr(k) for k in keys]


def enc_das_call(chunks, indices, proofs, roots) -> list:
    """The das_verify_samples argument plane: (chunks, indices,
    sibling-path rows, roots) — positional, matching the backend op."""
    return [
        [enc_bytes(c) for c in chunks],
        [int(i) for i in indices],
        [[enc_bytes(node) for node in path] for path in proofs],
        [enc_bytes(r) for r in roots],
    ]


def dec_das_call(chunks, indices, proofs, roots) -> tuple:
    return (
        [dec_bytes(c) for c in chunks],
        [int(i) for i in indices],
        [[dec_bytes(node) for node in path] for path in proofs],
        [dec_bytes(r) for r in roots],
    )


def enc_das_poly_call(commitments, index_rows, eval_rows, proofs,
                      ns) -> list:
    """The das_verify_multiproofs argument plane: (64-byte G1
    commitments, per-row sampled index sets, per-row claimed
    evaluations as hex field elements, 64-byte G1 multiproofs, domain
    sizes) — positional, matching the backend op."""
    return [
        [enc_bytes(c) for c in commitments],
        [[int(i) for i in row] for row in index_rows],
        [[hex(int(e)) for e in row] for row in eval_rows],
        [enc_bytes(p) for p in proofs],
        [int(n) for n in ns],
    ]


def dec_das_poly_call(commitments, index_rows, eval_rows, proofs,
                      ns) -> tuple:
    return (
        [dec_bytes(c) for c in commitments],
        [[int(i) for i in row] for row in index_rows],
        [[int(e, 16) for e in row] for row in eval_rows],
        [dec_bytes(p) for p in proofs],
        [int(n) for n in ns],
    )


# -- fleettrace span-batch codec (the shard_traceExport plane) -------------
# Finished tracer records travel as compact positional rows, not
# keyed objects: an export batch is the highest-volume payload on the
# control plane (hundreds of spans per flush) and the field names
# would dominate the wire bytes. `dur_us` is derived, so it is NOT
# shipped — the decoder recomputes it.

_JSON_SCALARS = (str, int, float, bool, type(None))

# The trace plane is invisible to tracing. A client span around
# `shard_traceExport` lands in the very export buffer the call is
# shipping — the drain can never go empty (a self-sustaining feedback
# loop) — and a handler span per batch floods the collector with
# meta-traces of its own transport; exemplar polls would evict the
# exemplars they read. Client and server both skip span creation for
# these methods.
TRACE_PLANE_METHODS = frozenset({
    "shard_traceExport", "shard_traceHandshake",
    "shard_traceAttribution", "shard_traceExemplars"})


def enc_span_tags(tags) -> Optional[dict]:
    """Span tags with non-JSON values coerced to repr: tags are an
    open dict (callers stash whatever helps debugging) and one exotic
    value must not poison a whole export batch at serialization time."""
    if not tags:
        return None
    return {str(k): (v if isinstance(v, _JSON_SCALARS) else repr(v))
            for k, v in tags.items()}


def enc_spans(records) -> list:
    """Tracer records -> positional rows
    ``[name, trace, span, parent, start, end, tid, tags]`` (monotonic
    seconds; the batch envelope carries the producer's clock anchor)."""
    return [[r["name"], r["trace"], r["span"], r["parent"],
             r["start"], r["end"], r["tid"], enc_span_tags(r["tags"])]
            for r in records]


def dec_spans(rows) -> list:
    out = []
    for name, trace, span, parent, start, end, tid, tags in rows:
        start = float(start)
        end = float(end)
        out.append({
            "name": str(name), "trace": int(trace), "span": int(span),
            "parent": None if parent is None else int(parent),
            "start": start, "end": end,
            "dur_us": round((end - start) * 1e6, 1),
            "tid": None if tid is None else int(tid),
            "tags": dict(tags) if tags else {},
        })
    return out


# -- shardp2p message codecs (type-tagged, for the cross-process relay) ----


def enc_p2p(data) -> tuple:
    """Message object -> (type tag, JSON payload)."""
    from gethsharding_tpu.p2p import messages as m

    if isinstance(data, m.CollationBodyRequest):
        return "CollationBodyRequest", {
            "chunkRoot": None if data.chunk_root is None
            else enc_bytes(data.chunk_root),
            "shardId": data.shard_id,
            "period": data.period,
            "proposer": None if data.proposer is None
            else enc_bytes(data.proposer),
            "signature": enc_bytes(data.signature),
        }
    if isinstance(data, m.CollationBodyResponse):
        return "CollationBodyResponse", {
            "headerHash": enc_bytes(data.header_hash),
            "body": enc_bytes(data.body),
        }
    if isinstance(data, m.ChunkProofRequest):
        return "ChunkProofRequest", {
            "chunkRoot": enc_bytes(data.chunk_root),
            "shardId": data.shard_id,
            "period": data.period,
            "index": data.index,
        }
    if isinstance(data, m.ChunkProofResponse):
        return "ChunkProofResponse", {
            "chunkRoot": enc_bytes(data.chunk_root),
            "index": data.index,
            "proof": [enc_bytes(node) for node in data.proof],
            "bodyLen": data.body_len,
        }
    if isinstance(data, m.DASCommitmentRequest):
        return "DASCommitmentRequest", {
            "shardId": data.shard_id,
            "period": data.period,
        }
    if isinstance(data, m.DASCommitmentResponse):
        return "DASCommitmentResponse", {
            "shardId": data.shard_id,
            "period": data.period,
            "chunkRoot": enc_bytes(data.chunk_root),
            "dasRoot": enc_bytes(data.das_root),
            "k": data.k,
            "n": data.n,
            "bodyLen": data.body_len,
            "polyCommitment": enc_bytes(data.poly_commitment),
            "signature": enc_bytes(data.signature),
        }
    if isinstance(data, m.DASampleRequest):
        return "DASampleRequest", {
            "dasRoot": enc_bytes(data.das_root),
            "indices": list(data.indices),
        }
    if isinstance(data, m.DASampleResponse):
        return "DASampleResponse", {
            "dasRoot": enc_bytes(data.das_root),
            "index": data.index,
            "chunk": enc_bytes(data.chunk),
            "proof": [enc_bytes(node) for node in data.proof],
        }
    if isinstance(data, m.DASMultiproofRequest):
        return "DASMultiproofRequest", {
            "dasRoot": enc_bytes(data.das_root),
            "indices": list(data.indices),
        }
    if isinstance(data, m.DASMultiproofResponse):
        return "DASMultiproofResponse", {
            "dasRoot": enc_bytes(data.das_root),
            "indices": list(data.indices),
            "chunks": [enc_bytes(c) for c in data.chunks],
            "proof": enc_bytes(data.proof),
        }
    from gethsharding_tpu.p2p.whisper import Envelope

    if isinstance(data, Envelope):
        return "WhisperEnvelope", {
            "expiry": data.expiry,
            "ttl": data.ttl,
            "topic": enc_bytes(data.topic),
            "ciphertext": enc_bytes(data.ciphertext),
            "nonce": data.nonce,
        }
    from gethsharding_tpu.p2p import discovery as disc

    if isinstance(data, disc.PeerTableRequest):
        return "PeerTableRequest", {}
    if isinstance(data, disc.PeerTableResponse):
        return "PeerTableResponse", {
            "announces": [_enc_announce(a) for a in data.announces],
        }
    from gethsharding_tpu.storage import netstore as ns

    if isinstance(data, ns.ChunkRequest):
        return "ChunkRequest", {"key": enc_bytes(data.key)}
    if isinstance(data, ns.ChunkDelivery):
        return "ChunkDelivery", {"key": enc_bytes(data.key),
                                 "span": data.span,
                                 "payload": enc_bytes(data.payload)}
    raise TypeError(f"no p2p wire codec for {type(data).__name__}")


def _enc_announce(ann) -> dict:
    return {"peerId": ann.peer_id, "account": ann.account,
            "host": ann.host, "port": ann.port, "seq": ann.seq,
            "sig": enc_bytes(ann.sig)}


def _dec_announce(obj: dict):
    from gethsharding_tpu.p2p import discovery as disc

    return disc.PeerAnnounce(
        peer_id=int(obj["peerId"]), account=str(obj["account"]),
        host=str(obj["host"]), port=int(obj["port"]), seq=int(obj["seq"]),
        sig=dec_bytes(obj["sig"]))


def dec_p2p(kind: str, payload: dict):
    from gethsharding_tpu.p2p import messages as m

    if kind == "CollationBodyRequest":
        return m.CollationBodyRequest(
            chunk_root=None if payload["chunkRoot"] is None
            else Hash32(dec_bytes(payload["chunkRoot"])),
            shard_id=payload["shardId"],
            period=payload["period"],
            proposer=None if payload["proposer"] is None
            else Address20(dec_bytes(payload["proposer"])),
            signature=dec_bytes(payload["signature"]),
        )
    if kind == "CollationBodyResponse":
        return m.CollationBodyResponse(
            header_hash=Hash32(dec_bytes(payload["headerHash"])),
            body=dec_bytes(payload["body"]),
        )
    if kind == "ChunkProofRequest":
        return m.ChunkProofRequest(
            chunk_root=Hash32(dec_bytes(payload["chunkRoot"])),
            shard_id=payload["shardId"],
            period=payload["period"],
            index=payload["index"],
        )
    if kind == "ChunkProofResponse":
        return m.ChunkProofResponse(
            chunk_root=Hash32(dec_bytes(payload["chunkRoot"])),
            index=payload["index"],
            proof=tuple(dec_bytes(node) for node in payload["proof"]),
            body_len=payload.get("bodyLen", 0),
        )
    if kind == "DASCommitmentRequest":
        return m.DASCommitmentRequest(
            shard_id=int(payload["shardId"]),
            period=int(payload["period"]),
        )
    if kind == "DASCommitmentResponse":
        return m.DASCommitmentResponse(
            shard_id=int(payload["shardId"]),
            period=int(payload["period"]),
            chunk_root=Hash32(dec_bytes(payload["chunkRoot"])),
            das_root=dec_bytes(payload["dasRoot"]),
            k=int(payload["k"]),
            n=int(payload["n"]),
            body_len=int(payload["bodyLen"]),
            poly_commitment=dec_bytes(payload.get("polyCommitment", "")),
            signature=dec_bytes(payload["signature"]),
        )
    if kind == "DASampleRequest":
        return m.DASampleRequest(
            das_root=dec_bytes(payload["dasRoot"]),
            indices=tuple(int(i) for i in payload["indices"]),
        )
    if kind == "DASampleResponse":
        return m.DASampleResponse(
            das_root=dec_bytes(payload["dasRoot"]),
            index=int(payload["index"]),
            chunk=dec_bytes(payload["chunk"]),
            proof=tuple(dec_bytes(node) for node in payload["proof"]),
        )
    if kind == "DASMultiproofRequest":
        return m.DASMultiproofRequest(
            das_root=dec_bytes(payload["dasRoot"]),
            indices=tuple(int(i) for i in payload["indices"]),
        )
    if kind == "DASMultiproofResponse":
        return m.DASMultiproofResponse(
            das_root=dec_bytes(payload["dasRoot"]),
            indices=tuple(int(i) for i in payload["indices"]),
            chunks=tuple(dec_bytes(c) for c in payload["chunks"]),
            proof=dec_bytes(payload["proof"]),
        )
    if kind == "WhisperEnvelope":
        from gethsharding_tpu.p2p.whisper import Envelope

        # coerce the int fields: a peer-supplied non-int would otherwise
        # detonate later inside the whisper daemon thread, not here at
        # the wire boundary where the caller's guard catches it
        return Envelope(
            expiry=int(payload["expiry"]),
            ttl=int(payload["ttl"]),
            topic=dec_bytes(payload["topic"]),
            ciphertext=dec_bytes(payload["ciphertext"]),
            nonce=int(payload["nonce"]),
        )
    if kind == "ChunkRequest":
        from gethsharding_tpu.storage import netstore as ns

        return ns.ChunkRequest(key=dec_bytes(payload["key"]))
    if kind == "ChunkDelivery":
        from gethsharding_tpu.storage import netstore as ns

        return ns.ChunkDelivery(key=dec_bytes(payload["key"]),
                                span=int(payload["span"]),
                                payload=dec_bytes(payload["payload"]))
    if kind == "PeerTableRequest":
        from gethsharding_tpu.p2p import discovery as disc

        return disc.PeerTableRequest()
    if kind == "PeerTableResponse":
        from gethsharding_tpu.p2p import discovery as disc

        return disc.PeerTableResponse(
            announces=tuple(_dec_announce(a)
                            for a in payload.get("announces", [])))
    raise ValueError(f"unknown p2p message type {kind!r}")


def enc_block(block) -> dict:
    return {"number": block.number, "hash": enc_bytes(block.hash),
            "parentHash": enc_bytes(block.parent_hash),
            "extra": enc_bytes(getattr(block, "extra", b"") or b"")}


def dec_block(obj: dict):
    from gethsharding_tpu.smc.chain import Block

    return Block(number=int(obj["number"]),
                 hash=Hash32(dec_bytes(obj["hash"])),
                 parent_hash=Hash32(dec_bytes(obj["parentHash"])),
                 extra=dec_bytes(obj.get("extra", "")))


def enc_receipt(receipt) -> dict:
    return {"txHash": enc_bytes(receipt.tx_hash), "status": receipt.status,
            "blockNumber": receipt.block_number}
