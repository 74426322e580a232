"""Persistent compilation cache + AOT warm start.

Whole-program XLA compilation is this framework's core bet, and every
elastic restart, reshape restore, planner candidate and inference
cold-start would otherwise re-pay the full trace+lower+compile.  Two
layers make compiled work durable across processes:

* **jax's own persistent compilation cache** (backend executables,
  donation kept) is on by default and placed by ONE rule,
  ``setup_xla_cache()``: ``JAX_COMPILATION_CACHE_DIR`` set -> jax
  reads it itself and this program sets no directory in code; unset
  -> ``<checkout>/.jax_cache``.  The path is part of jax's cache key,
  so it is fixed: never ``~``, a temporary name, a pid or the time.
* **the exec and text tiers** of this module serve only when
  ``PADDLE_TPU_COMPILE_CACHE`` names a directory (tests and
  ``tools/precompile.py`` do):

  - *exec tier* — serialized ``jax.export`` artifacts (StableHLO +
    calling convention) of a jitted function.  A warm process
    deserializes and runs ``jax.jit(exported.call)`` instead of
    re-tracing the Python model.  A hit does NOT donate its inputs,
    so the same commit runs a different program on its second start
    than on its first — which is why the tier is opt-in;
  - *text tier* — compiled (post-partitioner) HLO text keyed by the
    planner/audit lowering keys, so repeated ``tpu_lint --plan``/
    ``--hlo`` invocations on unchanged targets read disk instead of
    compiling dozens of candidates again.

Every entry is ONE file written through the resilience/manifest commit
discipline (``manifest.atomic_write``: tmp + fsync + os.replace) with
an embedded size+sha256 of the payload.  A reader that finds a torn or
corrupted entry (external damage, chaos-injected torn writes) moves it
aside to ``<entry>.quarantine`` and treats the lookup as a miss — a
torn entry can NEVER be loaded.  Writes are multi-process safe: two
processes racing on the same fingerprint both perform atomic replaces
of identical content.

Keys are content fingerprints over (jaxpr text with memory addresses
normalized out, static arguments, mesh axes, in/out shardings,
donation mask, jax version, backend, device count, and a hash of the
package sources — any code edit, or a stray untracked ``.py`` under
the package directory, invalidates conservatively).

``PADDLE_TPU_COMPILE_CACHE``: unset -> jax's cache only.  A path ->
the exec/text tiers in that directory, jax's cache still where the
rule above puts it.  ``0``/``off``/``false``/empty -> everything off,
jax's cache included (``jax_enable_compilation_cache`` False): a tool
that wants cold compiles turns the cache off, it does not move it.
The test suite runs this way so tier-1 is cache-independent.

Telemetry: every hit/miss/serialize/deserialize/quarantine emits a
``compile_cache`` event with bytes and latency; ``tools/run_report``
renders hit rates and estimated compile time saved.

Warm start: ``tools/precompile.py`` compiles a declared bucket set at
export time and writes a sidecar ``_PADDLE_PRECOMPILE.json`` next to a
checkpoint; ``warm_start(dir)`` (called by auto_checkpoint /
CheckpointManager.restore) pre-loads those entries so a restarted
worker's first step deserializes instead of recompiling, and
``tools/check_ckpt.py --deep`` audits the manifest against the cache.

This module imports jax lazily so stdlib-only consumers (check_ckpt)
can verify entries without a jax install.
"""
import hashlib
import json
import os
import re
import time

__all__ = [
    'enabled', 'cache_dir', 'setup_xla_cache', 'xla_cache_dir',
    'fingerprint', 'jaxpr_text',
    'jaxpr_fingerprint', 'get', 'put', 'get_text', 'put_text',
    'lookup_executable', 'store_executable', 'export_jit',
    'through_cache', 'bucket_pow2', 'stats', 'reset_stats',
    'PRECOMPILE_MANIFEST', 'write_precompile_manifest',
    'read_precompile_manifest', 'verify_precompile_manifest',
    'warm_start',
]

ENV_VAR = 'PADDLE_TPU_COMPILE_CACHE'
XLA_ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'
_DISABLE_VALUES = ('0', 'off', 'false', 'no', '')
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PRECOMPILE_MANIFEST = '_PADDLE_PRECOMPILE.json'
_FORMAT = 1
_ADDR_RE = re.compile(r'0x[0-9a-fA-F]+')

_stats = {}
_code_token_memo = None
_extra_dirs = []    # sidecar-recorded cache dirs (warm_start) lookups
#                     fall back to when the local dir misses
_NOT_APPLIED = object()
_xla_applied = _NOT_APPLIED  # the xla_cache_dir() last applied


def _switched_off():
    raw = os.environ.get(ENV_VAR)
    return raw is not None and raw.strip().lower() in _DISABLE_VALUES


def enabled():
    """True iff the exec/text tiers are active for this process."""
    return cache_dir() is not None


def cache_dir():
    """The exec/text tier directory (created lazily by put), or None
    unless PADDLE_TPU_COMPILE_CACHE names one.  Every compile choke
    point asks this before it builds, so this is also where jax's own
    cache gets placed (setup_xla_cache)."""
    setup_xla_cache()
    raw = os.environ.get(ENV_VAR)
    if raw is None or _switched_off():
        return None
    return os.path.abspath(os.path.expanduser(raw))


def xla_cache_dir():
    """Where jax's persistent compilation cache lives under the rule,
    or None when PADDLE_TPU_COMPILE_CACHE switches caching off."""
    if _switched_off():
        return None
    return os.environ.get(XLA_ENV_VAR) or \
        os.path.join(_CHECKOUT, '.jax_cache')


def setup_xla_cache():
    """Place jax's persistent compilation cache: the one function
    every entry point and compile choke point goes through.  With
    JAX_COMPILATION_CACHE_DIR set, jax has read it itself and no
    directory is set here; unset, the cache is <checkout>/.jax_cache.
    The persistence thresholds are the same either way (every module
    is kept, however quick or small).  Returns xla_cache_dir()."""
    global _xla_applied
    want = xla_cache_dir()
    if want == _xla_applied:
        return want
    import jax
    if want is None:
        jax.config.update('jax_enable_compilation_cache', False)
    else:
        if _xla_applied is None:
            # switched back on in-process (tests): undo our own off
            jax.config.update('jax_enable_compilation_cache', True)
        if not os.environ.get(XLA_ENV_VAR):
            jax.config.update('jax_compilation_cache_dir', want)
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          0.0)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes',
                          -1)
        # jax's key leaves an instruction's metadata out by default, so
        # a module that differs from a cached one only by a
        # jax.named_scope is served the OLD executable and its op_name
        # (what a profile's ops are attributed by) never shows: the key
        # sees metadata, with source paths relative to the checkout so
        # that another checkout of the same code still hits
        jax.config.update(
            'jax_compilation_cache_include_metadata_in_key', True)
        jax.config.update('jax_hlo_source_file_canonicalization_regex',
                          re.escape(_CHECKOUT + os.sep))
    # jax latches its cache decision at the FIRST compile; an eager op
    # before this ran would have latched "no cache" — reset so the
    # next compile re-reads the config
    from jax.experimental.compilation_cache import (
        compilation_cache as _jcc)
    _jcc.reset_cache()
    _xla_applied = want
    return want


# -- stats / telemetry --------------------------------------------------------

def stats():
    """Process-lifetime cache counters: {action_tier: count, ...} plus
    'saved_s' (estimated trace+lower seconds avoided by hits)."""
    out = dict(_stats)
    out.setdefault('saved_s', 0.0)
    return out


def reset_stats():
    _stats.clear()


def _note(action, tier, *, nbytes=None, dur_s=None, saved_s=None,
          name=None, fp=None):
    _stats[f'{action}_{tier}'] = _stats.get(f'{action}_{tier}', 0) + 1
    if saved_s:
        _stats['saved_s'] = round(_stats.get('saved_s', 0.0) + saved_s, 6)
    try:
        from .. import telemetry
        fields = {'action': action, 'tier': tier}
        if name:
            fields['name'] = name
        if fp:
            fields['key'] = fp[:16]
        if nbytes is not None:
            fields['bytes'] = int(nbytes)
        if dur_s is not None:
            fields['dur_s'] = round(dur_s, 6)
        if saved_s is not None:
            fields['saved_s'] = round(saved_s, 6)
        telemetry.event('compile_cache', **fields)
        telemetry.add(f'compile_cache.{action}')
    except Exception:       # pragma: no cover - defensive
        pass


# -- fingerprints -------------------------------------------------------------

def _code_token():
    """sha256 over every .py source of the paddle_tpu package: ANY code
    edit invalidates the cache (the conservative direction — a stale
    executable can never outlive the code that produced it)."""
    global _code_token_memo
    if _code_token_memo is not None:
        return _code_token_memo
    h = hashlib.sha256()
    try:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames.sort()
            dirnames[:] = [d for d in dirnames if d != '__pycache__']
            for f in sorted(filenames):
                if not f.endswith('.py'):
                    continue
                p = os.path.join(dirpath, f)
                h.update(os.path.relpath(p, root).encode())
                try:
                    with open(p, 'rb') as fh:
                        h.update(fh.read())
                except OSError:
                    continue
    except Exception:
        pass
    _code_token_memo = h.hexdigest()
    return _code_token_memo


def fingerprint(kind, **parts):
    """Stable hex fingerprint of (kind, parts) + the ambient compile
    environment (jax version, backend, device count, package sources).
    Values are hashed via repr — pass only shape/spec/flag data that
    reprs deterministically.  Returns None when anything goes wrong
    (callers then skip the cache)."""
    try:
        import jax
        h = hashlib.sha256()
        h.update(b'ptcc1\0')
        h.update(str(kind).encode())
        h.update(jax.__version__.encode())
        h.update(jax.default_backend().encode())
        h.update(str(jax.device_count()).encode())
        h.update(_code_token().encode())
        for k in sorted(parts):
            h.update(b'\0' + str(k).encode() + b'=')
            v = parts[k]
            h.update(v if isinstance(v, bytes) else repr(v).encode())
        return h.hexdigest()
    except Exception:
        return None


def jaxpr_text(fn, *example_args, **example_kwargs):
    """Abstract-trace `fn` and return its jaxpr pretty-print with
    memory addresses normalized out — the cross-process-stable content
    key for a traced program.  None on any trace failure."""
    try:
        import jax
        txt = str(jax.make_jaxpr(fn)(*example_args, **example_kwargs))
        return _ADDR_RE.sub('0x', txt)
    except Exception:
        return None


def jaxpr_fingerprint(kind, fn, example_args, extra=None):
    """fingerprint() over `fn`'s normalized jaxpr — the shared key
    helper every compile choke point (to_static / hapi / trainer /
    gptgen) routes through."""
    txt = jaxpr_text(fn, *example_args)
    if txt is None:
        return None
    return fingerprint(kind, jaxpr=txt.encode(), extra=extra)


def bucket_pow2(n, cap=None):
    """Next power of two >= n (>=1), optionally capped: the decode
    prompt-length bucketing that keeps the compiled-module set finite."""
    n = max(1, int(n))
    p = 1 << (n - 1).bit_length()
    if cap is not None:
        p = min(p, int(cap))
    return max(p, n)


# -- entry store (one atomic file per entry) ----------------------------------

def _entry_path(tier, fp):
    d = cache_dir()
    if d is None:
        return None
    return os.path.join(d, f'{tier}-{fp}.ptcc')


def _quarantine(path):
    try:
        os.replace(path, path + '.quarantine')
    except OSError:
        try:
            os.unlink(path)
        except OSError:
            pass


def put(tier, fp, payload, meta=None, name=None):
    """Atomically commit one cache entry.  The write goes through
    ``resilience.manifest.atomic_write`` — the same tmp+fsync+replace
    commit discipline (and the same chaos fault seam) as checkpoint
    manifests — with the payload's size+sha256 embedded in the header
    so readers can prove integrity.  Never raises; False on failure."""
    path = _entry_path(tier, fp) if fp else None
    if path is None or payload is None:
        return False
    t0 = time.perf_counter()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        header = {
            'format': _FORMAT, 'tier': tier, 'fingerprint': fp,
            'payload_size': len(payload),
            'payload_sha256': hashlib.sha256(payload).hexdigest(),
            'meta': dict(meta or {}),
        }
        hb = json.dumps(header, sort_keys=True).encode()
        from ..resilience import manifest as _manifest
        _manifest.atomic_write(
            path, lambda f: (f.write(hb), f.write(b'\n'),
                             f.write(payload)),
            mode='wb', prefix='.cc_tmp')
    except Exception:
        return False
    _note('serialize', tier, nbytes=len(payload),
          dur_s=time.perf_counter() - t0, name=name, fp=fp)
    return True


def get(tier, fp, name=None):
    """-> (payload_bytes, header) or None.  A torn/corrupt entry is
    quarantined (renamed aside) and reads as a miss — it never loads."""
    if fp is None:
        return None
    path = _entry_path(tier, fp)
    if path is None:
        return None
    t0 = time.perf_counter()
    data = None
    try:
        with open(path, 'rb') as f:
            data = f.read()
    except OSError:
        # a restore may have registered the precompile host's cache
        # dir (warm_start): a cross-host AOT set still deserializes
        alt = _find_entry(_extra_dirs, tier, fp)
        if alt is not None:
            try:
                with open(alt, 'rb') as f:
                    data = f.read()
                path = alt
            except OSError:
                data = None
    if data is None:
        _note('miss', tier, name=name, fp=fp)
        return None
    got = _parse_entry(data, tier, fp)
    if got is None:
        _quarantine(path)
        _note('quarantine', tier, nbytes=len(data), name=name, fp=fp)
        # the caller proceeds to recompile, so a quarantined lookup is
        # ALSO a miss — otherwise hit rates exclude damaged entries
        # from the denominator and overstate cache health exactly when
        # the cache is broken
        _note('miss', tier, name=name, fp=fp)
        return None
    payload, header = got
    # saved_s rides only on the exec tier's 'deserialize' event (one
    # per warm lookup) — carrying it here too would double-count the
    # compile time saved in stats() and run_report
    _note('hit', tier, nbytes=len(payload),
          dur_s=time.perf_counter() - t0, name=name, fp=fp)
    return payload, header


def _parse_entry(data, tier, fp):
    """Verify one entry's framing + integrity; None = torn/corrupt."""
    try:
        nl = data.index(b'\n')
        header = json.loads(data[:nl].decode())
        payload = data[nl + 1:]
        if header.get('format') != _FORMAT:
            return None
        if header.get('tier') != tier or header.get('fingerprint') != fp:
            return None
        if len(payload) != header.get('payload_size'):
            return None
        if hashlib.sha256(payload).hexdigest() != \
                header.get('payload_sha256'):
            return None
        return payload, header
    except Exception:
        return None


def get_text(fp, name=None):
    got = get('hlo', fp, name=name)
    if got is None:
        return None
    try:
        return got[0].decode()
    except UnicodeDecodeError:
        return None


def put_text(fp, text, meta=None, name=None):
    return put('hlo', fp, text.encode(), meta=meta, name=name)


# -- executable (jax.export) tier ---------------------------------------------

def _abstract(tree):
    import jax

    def leaf(v):
        if hasattr(v, 'shape') and hasattr(v, 'dtype'):
            # keep mesh shardings on the avals: the export (and the
            # aot_compile seeding) must describe the SAME partitioned
            # program the warm process will call with sharded arrays
            sh = getattr(v, 'sharding', None)
            if sh is not None and hasattr(sh, 'mesh'):
                try:
                    return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                sharding=sh)
                except Exception:
                    pass
            return jax.ShapeDtypeStruct(v.shape, v.dtype)
        return v

    return jax.tree_util.tree_map(leaf, tree)


def lookup_executable(fp, name=None):
    """exec-tier lookup: deserialize the jax.export artifact and wrap
    it as a jitted callable.  None on miss or deserialize failure.

    The returned callable runs the EXACT serialized StableHLO (same
    numerics as the original compile) but does not donate its inputs —
    the warm path trades that sliver of HBM for skipping the trace."""
    got = get('exec', fp, name=name)
    if got is None:
        return None
    payload, header = got
    t0 = time.perf_counter()
    try:
        import jax
        from jax import export as _jexport
        exp = _jexport.deserialize(bytearray(payload))
        fn = jax.jit(exp.call)
    except Exception:
        # verified bytes that no longer deserialize = environment
        # drift the fingerprint missed; drop them so the next miss
        # re-serializes a loadable artifact
        path = _entry_path('exec', fp)
        if path:
            _quarantine(path)
        _note('quarantine', 'exec', name=name, fp=fp)
        return None
    _note('deserialize', 'exec', nbytes=len(payload),
          dur_s=time.perf_counter() - t0,
          saved_s=(header.get('meta') or {}).get('export_s'),
          name=name, fp=fp)
    # warm starts skip every compile choke point downstream, so the
    # memory observatory would go blind on exactly the restarted
    # processes that need it — armed-only (extra lower+compile,
    # amortized by the XLA persistent cache the aot store warmed)
    from ..telemetry import memory as _mem
    if _mem.armed():
        try:
            avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in exp.in_avals]
            _mem.maybe_note_compiled(name or f'fp:{str(fp)[:12]}',
                                     fn, avals, source='warm_start')
        except Exception:
            pass
    return fn


def store_executable(fp, jitted, example_args, name=None, meta=None,
                     aot_compile=False):
    """Export `jitted` (a jax.jit object) over abstract versions of
    `example_args`, serialize, and commit under `fp`.  The export pays
    one extra trace+lower — the population cost a warm process saves.
    Never raises; False on failure (e.g. non-exportable custom calls).

    aot_compile=True additionally XLA-compiles the deserialized form
    (lower+compile, no execution) so the BACKEND executable lands in
    jax's persistent cache too — tools/precompile.py pays this once at
    export time and a restarted worker's first step then skips trace,
    lower AND the XLA optimization passes."""
    if fp is None or not enabled():
        return False
    try:
        import jax
        from jax import export as _jexport
        t0 = time.perf_counter()
        abstract = _abstract(tuple(example_args))
        exp = _jexport.export(jitted)(*abstract)
        blob = exp.serialize()
        export_s = time.perf_counter() - t0
        if aot_compile:
            compiled = jax.jit(exp.call).lower(*abstract).compile()
            # memory observatory rides the AOT compile we just paid
            # for — FREE extraction on every cold-miss population
            from ..telemetry import memory as _mem
            _mem.note_compiled(name or f'fp:{str(fp)[:12]}', compiled,
                               source='compile_cache')
    except Exception:
        return False
    doc = dict(meta or {})
    doc.setdefault('name', name)
    doc['export_s'] = round(export_s, 6)
    return put('exec', fp, bytes(blob), meta=doc, name=name)


def _with_fallback(warm, cold, name=None):
    """Wrap a deserialized executable so an aval mismatch (the warm
    module is shape-rigid where jax.jit would have retraced — ragged
    last batch, new to_static shapes, x64 flips) degrades to the cold
    jit instead of crashing; the cold path then retraces per shape
    exactly as an uncached run would.  `.lower` passes through to the
    warm module for the AOT consumers (compiled_text / census)."""
    state = {'warm': True}

    def call(*args, **kwargs):
        if state['warm']:
            try:
                return warm(*args, **kwargs)
            except Exception:
                # one-way: any failure of the deserialized module
                # (wrong avals, environment drift) retires it for this
                # callable — purity makes the retry safe (warm hits
                # never donate their inputs)
                state['warm'] = False
                _note('fallback', 'exec', name=name)
        return cold(*args, **kwargs)

    call.lower = warm.lower
    return call


def through_cache(jitted, example_args, *, fp, name=None):
    """The standard choke-point pattern: on a hit, the deserialized
    executable replaces `jitted` (with `jitted` kept as the aval-
    mismatch fallback); on a miss, `jitted` is exported into the cache
    and returned unchanged (the cold path keeps its exact current
    semantics, donation included).  Never raises."""
    if fp is None or not enabled():
        return jitted
    try:
        hit = lookup_executable(fp, name=name)
        if hit is not None:
            return _with_fallback(hit, jitted, name=name)
        # aot_compile: also XLA-compile the deserialized form now, so
        # the warm process's module is already in jax's persistent XLA
        # cache — the first-ever population pays ~one extra backend
        # compile; every later restart skips trace, lower AND XLA
        store_executable(fp, jitted, example_args, name=name,
                         aot_compile=True)
        return jitted
    except Exception:
        return jitted


def export_jit(fn, example_args, *, fp, name=None, jit_kwargs=None):
    """Export-primary jit: trace ONCE through jax.export, persist the
    artifact, and execute via the deserially-identical wrapped call.
    For giant traces (gptgen decode) this avoids the double trace
    ``through_cache`` pays on a miss.  Falls back to plain jax.jit
    when the cache is off or export fails."""
    import jax
    jitted = jax.jit(fn, **(jit_kwargs or {}))
    if fp is None or not enabled():
        return jitted
    try:
        from jax import export as _jexport
        t0 = time.perf_counter()
        exp = _jexport.export(jitted)(*_abstract(tuple(example_args)))
        blob = exp.serialize()
        export_s = time.perf_counter() - t0
        put('exec', fp, bytes(blob),
            meta={'name': name, 'export_s': round(export_s, 6)},
            name=name)
        return _with_fallback(jax.jit(exp.call), jitted, name=name)
    except Exception:
        return jitted


# -- AOT warm start: precompile sidecar manifests -----------------------------

def write_precompile_manifest(directory, entries, meta=None):
    """Commit a sidecar manifest next to a checkpoint recording the
    AOT bucket set precompiled for it: [{'tier', 'fingerprint',
    'description'}, ...].  Atomic (same discipline as cache entries);
    check_ckpt --deep audits it, warm_start() preloads it."""
    from ..resilience import manifest as _manifest
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    doc = {'format': _FORMAT, 'entries': list(entries),
           'cache_dir': cache_dir()}
    if meta:
        doc.update(meta)
    _manifest.atomic_write(
        os.path.join(directory, PRECOMPILE_MANIFEST),
        lambda f: json.dump(doc, f, indent=1, sort_keys=True),
        prefix='.pc_tmp')
    return doc


def read_precompile_manifest(directory):
    try:
        with open(os.path.join(os.path.abspath(directory),
                               PRECOMPILE_MANIFEST)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _candidate_dirs(doc):
    """Cache dirs an AOT entry may live in: the locally-configured one
    plus the one the precompile host recorded in the sidecar — a
    checkpoint audited/restored on a different host must not read as
    'broken AOT set' just because the env var points elsewhere."""
    dirs = []
    for d in (cache_dir(), (doc or {}).get('cache_dir')):
        if d and d not in dirs:
            dirs.append(d)
    return dirs


def _find_entry(dirs, tier, fp):
    for d in dirs:
        p = os.path.join(d, f'{tier}-{fp}.ptcc')
        if os.path.isfile(p):
            return p
    return None


def verify_precompile_manifest(directory):
    """-> (ok, errors): every manifest-listed entry must resolve to a
    committed, integrity-verified cache entry — in the locally
    configured cache or the one the sidecar records (jax-free: only
    file reads + sha256, so check_ckpt can audit a restore target's
    AOT set from any machine)."""
    doc = read_precompile_manifest(directory)
    if doc is None:
        return False, [f'missing or unreadable {PRECOMPILE_MANIFEST}']
    dirs = _candidate_dirs(doc)
    if not dirs:
        return False, [f'{ENV_VAR} is disabled and the sidecar records '
                       'no cache dir: the AOT set cannot be audited '
                       '(or used) on this host']
    errors = []
    for e in doc.get('entries', []):
        tier, fp = e.get('tier'), e.get('fingerprint')
        tag = e.get('description') or f'{tier}-{str(fp)[:16]}'
        path = _find_entry(dirs, tier, fp) if fp else None
        if path is None:
            errors.append(f'{tag}: cache entry missing')
            continue
        try:
            with open(path, 'rb') as f:
                data = f.read()
        except OSError as err:
            errors.append(f'{tag}: unreadable ({err})')
            continue
        if _parse_entry(data, tier, fp) is None:
            errors.append(f'{tag}: torn or corrupt cache entry')
    return not errors, errors


def warm_start(directory, name=None):
    """Verify-and-prewarm the sidecar manifest's AOT set: each listed
    entry is read once (quarantining torn ones and pulling the rest
    into the OS page cache) so the restarted worker's first compile
    lookups are disk-warm.  Nothing is retained in process RAM — a
    stale sidecar (code/jax drift re-keyed the fingerprints) must not
    pin hundreds of MB of serialized artifacts that will never be
    looked up.  Called from auto_checkpoint / CheckpointManager
    restore; silent no-op without a manifest.  Returns the count of
    verified entries."""
    if not enabled():
        return 0
    doc = read_precompile_manifest(directory)
    if doc is None:
        return 0
    dirs = _candidate_dirs(doc)
    local = cache_dir()
    for d in dirs:
        if d != local and d not in _extra_dirs:
            # remember the precompile host's cache dir so later
            # lookups fall back to it when the local dir misses
            _extra_dirs.append(d)
    n = 0
    t0 = time.perf_counter()
    for e in doc.get('entries', []):
        tier, fp = e.get('tier'), e.get('fingerprint')
        if not tier or not fp:
            continue
        path = _find_entry(dirs, tier, fp)
        if path is None:
            continue
        try:
            with open(path, 'rb') as f:
                data = f.read()
        except OSError:
            continue
        if _parse_entry(data, tier, fp) is None:
            _quarantine(path)
            _note('quarantine', tier, fp=fp)
            continue
        n += 1
    if n:
        _stats['warm_start'] = _stats.get('warm_start', 0) + n
        try:
            from .. import telemetry
            telemetry.event(
                'compile_cache', action='warm_start', tier='exec',
                count=n, dur_s=round(time.perf_counter() - t0, 6),
                name=name or os.path.basename(os.path.abspath(directory)))
            telemetry.add('compile_cache.warm_start', n)
        except Exception:       # pragma: no cover - defensive
            pass
    return n
