// PJRT-tier native serving: load an exported StableHLO inference module
// through any PJRT C-API plugin (.so exporting GetPjrtApi) and execute it
// on that plugin's device — TPU serving with no Python in the process.
//
// This is the TPU-native analog of the reference's C++ inference path
// (paddle/inference/io.h:32 Load + Executor::Run) and closes the loop on
// SURVEY §7 step 2's "PJRT C API where native code is required": the
// device/memory layer the reference implements with platform/ +
// memory/buddy_allocator is the PJRT client here — buffers, transfers,
// compilation, execution, all through the stable C ABI.
//
// Inputs: <model_dir>/model.stablehlo (textual MLIR emitted by
// fluid.io.save_inference_model(..., export_stablehlo=True)) and
// model.stablehlo.json ({"inputs": [{name, shape, dtype, lod?}],
// "params": [{name, shape, dtype}], "outputs": [{shape, dtype}]}).
// Parameters are module ARGUMENTS: each is loaded from the CRC-framed
// tensor file <model_dir>/<name> (the save_persistables artifact) and
// uploaded to the device ONCE at create time — so the module text stays
// small at any model size and re-export is not needed per checkpoint.
// Feeds are dtype-tagged (float32/int32/int64); sequence feeds appear as
// a data input plus an int32 "<name>.lengths" input.

#include <dlfcn.h>

#include <cstring>
#include <fstream>
#include <memory>
#include <sstream>
#include <string>
#include <vector>

#include "json.h"
#include "tensor_file.h"
#include "xla/pjrt/c/pjrt_c_api.h"

namespace ptpu_pjrt {
namespace {

thread_local std::string g_err;

using ptpu::read_file;

PJRT_Buffer_Type dtype_to_pjrt(const std::string& dt) {
  if (dt == "float32") return PJRT_Buffer_Type_F32;
  if (dt == "int32") return PJRT_Buffer_Type_S32;
  if (dt == "int64") return PJRT_Buffer_Type_S64;
  if (dt == "bfloat16") return PJRT_Buffer_Type_BF16;
  if (dt == "float64") return PJRT_Buffer_Type_F64;
  if (dt == "float16") return PJRT_Buffer_Type_F16;
  if (dt == "int8") return PJRT_Buffer_Type_S8;
  if (dt == "uint8") return PJRT_Buffer_Type_U8;
  if (dt == "bool") return PJRT_Buffer_Type_PRED;
  throw std::runtime_error("unsupported dtype " + dt);
}

struct IoSpec {
  std::string name;
  std::string file;    // tensor file (params only; defaults to name)
  std::vector<int64_t> shape;
  std::string dtype;
};

struct Meta {
  std::vector<IoSpec> inputs;
  std::vector<IoSpec> params;
  std::vector<IoSpec> outputs;
};

void parse_iospec(const ptpu::JsonPtr& e, IoSpec* s, bool named) {
  if (named) s->name = e->at("name")->s;
  s->file = e->get("file") ? e->at("file")->s : s->name;
  s->dtype = e->get("dtype") ? e->at("dtype")->s : "float32";
  if (e->get("shape"))
    for (auto& d : e->at("shape")->arr) s->shape.push_back(d->i);
}

Meta parse_meta(const std::string& text) {
  ptpu::JsonParser p(text);
  auto root = p.parse();
  Meta m;
  for (auto& e : root->at("inputs")->arr) {
    IoSpec s;
    parse_iospec(e, &s, true);
    m.inputs.push_back(std::move(s));
  }
  if (root->get("params"))
    for (auto& e : root->at("params")->arr) {
      IoSpec s;
      parse_iospec(e, &s, true);
      m.params.push_back(std::move(s));
    }
  for (auto& e : root->at("outputs")->arr) {
    IoSpec s;
    parse_iospec(e, &s, false);
    m.outputs.push_back(std::move(s));
  }
  return m;
}

struct Runner {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  Meta meta;
  std::vector<PJRT_Buffer*> param_bufs;   // device-resident, upload once
  // last forward's outputs, copied to host (raw bytes, meta dtype)
  std::vector<std::vector<int64_t>> out_shapes;
  std::vector<std::string> out_dtypes;
  std::vector<std::vector<char>> out_raw;
  bool out_dtypes_verified = false;  // element-type check latched once

  ~Runner();
  void check(PJRT_Error* err, const char* what);
  void load(const std::string& model_dir, const std::string& plugin);
  PJRT_Buffer* upload(const void* data, const std::string& dtype,
                      const std::vector<int64_t>& dims, const char* what);
  void forward(const void* const* inputs);
  void await_event(PJRT_Event* ev, const char* what);
  void destroy_buffer(PJRT_Buffer* b);
};

// RAII: every PJRT buffer created during forward() is destroyed even when
// a check() throws mid-flight — a serving loop that retries on error must
// not leak device HBM
struct BufferGuard {
  Runner* r;
  std::vector<PJRT_Buffer*>* bufs;
  ~BufferGuard() {
    for (auto* b : *bufs)
      if (b) r->destroy_buffer(b);
  }
};

void Runner::await_event(PJRT_Event* ev, const char* what) {
  if (!ev) return;
  PJRT_Event_Await_Args aw;
  std::memset(&aw, 0, sizeof(aw));
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  PJRT_Error* err = api->PJRT_Event_Await(&aw);
  PJRT_Event_Destroy_Args ed;
  std::memset(&ed, 0, sizeof(ed));
  ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  ed.event = ev;
  api->PJRT_Event_Destroy(&ed);
  check(err, what);
}

void Runner::destroy_buffer(PJRT_Buffer* b) {
  PJRT_Buffer_Destroy_Args bd;
  std::memset(&bd, 0, sizeof(bd));
  bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  bd.buffer = b;
  api->PJRT_Buffer_Destroy(&bd);
}

void Runner::check(PJRT_Error* err, const char* what) {
  if (!err) return;
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = err;
  api->PJRT_Error_Message(&m);
  std::string msg = std::string(what) + ": " +
                    std::string(m.message, m.message_size);
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  api->PJRT_Error_Destroy(&d);
  throw std::runtime_error(msg);
}

void Runner::load(const std::string& model_dir, const std::string& plugin) {
  meta = parse_meta(read_file(model_dir + "/model.stablehlo.json"));
  std::string code = read_file(model_dir + "/model.stablehlo");

  dl = dlopen(plugin.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!dl) throw std::runtime_error(std::string("dlopen: ") + dlerror());
  auto get_api = (const PJRT_Api* (*)())dlsym(dl, "GetPjrtApi");
  if (!get_api) throw std::runtime_error("plugin has no GetPjrtApi");
  api = get_api();

  PJRT_Plugin_Initialize_Args pi;
  std::memset(&pi, 0, sizeof(pi));
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  check(api->PJRT_Plugin_Initialize(&pi), "plugin init");

  // plugin-specific client options: standard libtpu/CPU plugins need
  // none; a plugin that takes create options reads NamedValues.
  // Sourced from $PTPU_PJRT_CREATE_OPTIONS (JSON object of str|int),
  // mirroring how jax passes plugin options at register time.
  std::vector<PJRT_NamedValue> nvs;
  std::vector<std::string> nv_keys, nv_strs;  // stable storage
  std::vector<int64_t> nv_ints;
  ptpu::JsonPtr opt_root;
  const char* opt_env = getenv("PTPU_PJRT_CREATE_OPTIONS");
  std::string opt_text = opt_env ? opt_env : "";
  if (!opt_text.empty()) {
    ptpu::JsonParser op(opt_text);
    opt_root = op.parse();
    nv_keys.reserve(opt_root->obj.size());
    nv_strs.reserve(opt_root->obj.size());
    nv_ints.reserve(opt_root->obj.size());
    for (auto& kv : opt_root->obj) {
      nv_keys.push_back(kv.first);
      PJRT_NamedValue nv;
      std::memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = nv_keys.back().c_str();
      nv.name_size = nv_keys.back().size();
      if (kv.second->type == ptpu::Json::STRING) {
        nv_strs.push_back(kv.second->s);
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = nv_strs.back().c_str();
        nv.value_size = nv_strs.back().size();
      } else if (kv.second->type == ptpu::Json::INT) {
        nv_ints.push_back(kv.second->i);
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = nv_ints.back();
        nv.value_size = 1;
      } else {
        throw std::runtime_error("create option " + kv.first +
                                 ": only string/int supported");
      }
      nvs.push_back(nv);
    }
  }

  PJRT_Client_Create_Args cc;
  std::memset(&cc, 0, sizeof(cc));
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cc.create_options = nvs.empty() ? nullptr : nvs.data();
  cc.num_options = nvs.size();
  check(api->PJRT_Client_Create(&cc), "client create");
  client = cc.client;

  PJRT_Client_AddressableDevices_Args ad;
  std::memset(&ad, 0, sizeof(ad));
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = client;
  check(api->PJRT_Client_AddressableDevices(&ad), "devices");
  if (ad.num_addressable_devices == 0)
    throw std::runtime_error("no addressable devices");
  device = ad.addressable_devices[0];

  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = code.data();
  prog.code_size = code.size();
  static const char kFmt[] = "mlir";
  prog.format = kFmt;
  prog.format_size = sizeof(kFmt) - 1;

  // hand-encoded CompileOptionsProto: executable_build_options(field 3) {
  //   num_replicas(4)=1, num_partitions(5)=1 }
  static const char kOpts[] = {0x1a, 0x04, 0x20, 0x01, 0x28, 0x01};

  PJRT_Client_Compile_Args co;
  std::memset(&co, 0, sizeof(co));
  co.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  co.client = client;
  co.program = &prog;
  co.compile_options = kOpts;
  co.compile_options_size = sizeof(kOpts);
  check(api->PJRT_Client_Compile(&co), "compile");
  exec = co.executable;

  // trust the compiled executable, not the json, for the output count —
  // a stale/hand-edited meta undercounting outputs would otherwise make
  // Execute write output buffer pointers past the end of out_bufs
  PJRT_LoadedExecutable_GetExecutable_Args ge;
  std::memset(&ge, 0, sizeof(ge));
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = exec;
  check(api->PJRT_LoadedExecutable_GetExecutable(&ge), "get executable");
  PJRT_Executable_NumOutputs_Args no;
  std::memset(&no, 0, sizeof(no));
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.executable = ge.executable;
  PJRT_Error* no_err = api->PJRT_Executable_NumOutputs(&no);
  {
    // the queried executable is caller-owned — release it before any throw
    PJRT_Executable_Destroy_Args ed;
    std::memset(&ed, 0, sizeof(ed));
    ed.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    ed.executable = ge.executable;
    api->PJRT_Executable_Destroy(&ed);
  }
  check(no_err, "num outputs");
  if (no.num_outputs != meta.outputs.size())
    throw std::runtime_error(
        "model.stablehlo.json outputs (" +
        std::to_string(meta.outputs.size()) +
        ") disagree with compiled executable (" +
        std::to_string(no.num_outputs) + ") — stale meta?");

  // parameters: read each CRC-framed tensor file, upload once.  A dtype
  // mismatch between file and meta is a stale-export error, not a cast.
  param_bufs.reserve(meta.params.size());
  for (auto& p : meta.params) {
    ptpu::RawTensor t = ptpu::parse_tensor_raw(
        ptpu::unframe(read_file(model_dir + "/" + p.file), p.name), p.name);
    if (t.dtype != p.dtype)
      throw std::runtime_error(
          "param " + p.name + ": file dtype " + t.dtype +
          " != meta dtype " + p.dtype + " (stale export?)");
    if (t.shape != p.shape)
      throw std::runtime_error("param " + p.name +
                               ": file/meta shape mismatch");
    param_bufs.push_back(
        upload(t.data.data(), p.dtype, p.shape, p.name.c_str()));
  }
}

PJRT_Buffer* Runner::upload(const void* data, const std::string& dtype,
                            const std::vector<int64_t>& dims,
                            const char* what) {
  PJRT_Client_BufferFromHostBuffer_Args hb;
  std::memset(&hb, 0, sizeof(hb));
  hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  hb.client = client;
  hb.data = data;
  hb.type = dtype_to_pjrt(dtype);
  hb.dims = dims.data();
  hb.num_dims = dims.size();
  hb.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  hb.device = device;
  check(api->PJRT_Client_BufferFromHostBuffer(&hb), what);
  await_event(hb.done_with_host_buffer, what);
  return hb.buffer;
}

void Runner::forward(const void* const* inputs) {
  size_t n = meta.inputs.size();
  std::vector<PJRT_Buffer*> in_bufs(n, nullptr);
  size_t n_out = meta.outputs.size();
  std::vector<PJRT_Buffer*> out_bufs(n_out, nullptr);
  BufferGuard in_guard{this, &in_bufs};
  BufferGuard out_guard{this, &out_bufs};

  for (size_t i = 0; i < n; ++i)
    in_bufs[i] = upload(inputs[i], meta.inputs[i].dtype,
                        meta.inputs[i].shape, "h2d");
  // argument order matches the exported function: params then feeds
  std::vector<PJRT_Buffer*> args(param_bufs);
  args.insert(args.end(), in_bufs.begin(), in_bufs.end());
  PJRT_Buffer* const* arg_list = args.data();
  PJRT_Buffer** out_list = out_bufs.data();
  PJRT_Event* done = nullptr;

  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof(ex));
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = exec;
  ex.options = &opts;
  ex.argument_lists = &arg_list;
  ex.num_devices = 1;
  ex.num_args = args.size();
  ex.output_lists = &out_list;
  ex.device_complete_events = &done;
  check(api->PJRT_LoadedExecutable_Execute(&ex), "execute");
  await_event(done, "execute await");

  out_shapes.assign(n_out, {});
  out_dtypes.assign(n_out, "");
  out_raw.assign(n_out, {});
  for (size_t i = 0; i < n_out; ++i) {
    PJRT_Buffer_Dimensions_Args dm;
    std::memset(&dm, 0, sizeof(dm));
    dm.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    dm.buffer = out_bufs[i];
    check(api->PJRT_Buffer_Dimensions(&dm), "dims");
    out_shapes[i].assign(dm.dims, dm.dims + dm.num_dims);
    int64_t numel = 1;
    for (auto d : out_shapes[i]) numel *= d;
    out_dtypes[i] = meta.outputs[i].dtype;
    // Never trust the meta dtype for the d2h byte width: a stale or
    // hand-edited model.stablehlo.json would silently reinterpret the
    // bytes.  Verify against the executable's actual element type —
    // invariant for a compiled executable, so latched after the first
    // forward rather than paid per call.
    if (!out_dtypes_verified) {
      PJRT_Buffer_ElementType_Args et;
      std::memset(&et, 0, sizeof(et));
      et.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
      et.buffer = out_bufs[i];
      check(api->PJRT_Buffer_ElementType(&et), "element_type");
      bool mismatch;
      try {
        mismatch = et.type != dtype_to_pjrt(out_dtypes[i]);
      } catch (const std::exception&) {
        mismatch = true;  // meta dtype not even mappable
      }
      if (mismatch)
        throw std::runtime_error(
            "output " + std::to_string(i) + ": meta dtype '" +
            out_dtypes[i] + "' does not match the compiled buffer's "
            "element type (" + std::to_string((int)et.type) +
            ") — regenerate model.stablehlo.json");
    }
    int64_t w = ptpu::dtype_width(out_dtypes[i]);
    out_raw[i].resize(numel * w);

    PJRT_Buffer_ToHostBuffer_Args th;
    std::memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = out_bufs[i];
    th.dst = out_raw[i].data();
    th.dst_size = out_raw[i].size();
    check(api->PJRT_Buffer_ToHostBuffer(&th), "d2h");
    await_event(th.event, "d2h await");
  }
  out_dtypes_verified = true;
  // in/out buffers are destroyed by the BufferGuards (also on throw)
}

Runner::~Runner() {
  if (api)
    for (auto* b : param_bufs)
      if (b) destroy_buffer(b);
  if (api && exec) {
    PJRT_LoadedExecutable_Destroy_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    a.executable = exec;
    api->PJRT_LoadedExecutable_Destroy(&a);
  }
  if (api && client) {
    PJRT_Client_Destroy_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    a.client = client;
    api->PJRT_Client_Destroy(&a);
  }
  // the plugin .so stays loaded (unloading PJRT plugins is not safe)
}

}  // namespace
}  // namespace ptpu_pjrt

extern "C" {

const char* ptpu_pjrt_last_error() { return ptpu_pjrt::g_err.c_str(); }

void* ptpu_pjrt_create(const char* model_dir, const char* plugin_path) {
  auto r = std::make_unique<ptpu_pjrt::Runner>();
  try {
    r->load(model_dir, plugin_path);
    return r.release();
  } catch (const std::exception& e) {
    ptpu_pjrt::g_err = e.what();
    return nullptr;
  }
}

int ptpu_pjrt_num_inputs(void* h) {
  return (int)((ptpu_pjrt::Runner*)h)->meta.inputs.size();
}
const char* ptpu_pjrt_input_name(void* h, int i) {
  return ((ptpu_pjrt::Runner*)h)->meta.inputs.at(i).name.c_str();
}
const char* ptpu_pjrt_input_dtype(void* h, int i) {
  return ((ptpu_pjrt::Runner*)h)->meta.inputs.at(i).dtype.c_str();
}
int ptpu_pjrt_num_outputs(void* h) {
  return (int)((ptpu_pjrt::Runner*)h)->meta.outputs.size();
}

// dtype-tagged forward: inputs[i] points at data of
// ptpu_pjrt_input_dtype(h, i), in model.stablehlo.json order; shapes are
// fixed at export time
int ptpu_pjrt_forward_ex(void* h, const void* const* inputs) {
  try {
    ((ptpu_pjrt::Runner*)h)->forward(inputs);
    return 0;
  } catch (const std::exception& e) {
    ptpu_pjrt::g_err = e.what();
    return 1;
  }
}

// legacy float32-only entry: valid only when every input is float32
int ptpu_pjrt_forward(void* h, const float* const* inputs) {
  auto* r = (ptpu_pjrt::Runner*)h;
  for (auto& s : r->meta.inputs)
    if (s.dtype != "float32") {
      ptpu_pjrt::g_err = "input " + s.name + " is " + s.dtype +
                         ": use ptpu_pjrt_forward_ex";
      return 1;
    }
  return ptpu_pjrt_forward_ex(h, (const void* const*)inputs);
}

int ptpu_pjrt_output_rank(void* h, int i) {
  return (int)((ptpu_pjrt::Runner*)h)->out_shapes.at(i).size();
}
const int64_t* ptpu_pjrt_output_shape(void* h, int i) {
  return ((ptpu_pjrt::Runner*)h)->out_shapes.at(i).data();
}
const char* ptpu_pjrt_output_dtype(void* h, int i) {
  return ((ptpu_pjrt::Runner*)h)->out_dtypes.at(i).c_str();
}
const void* ptpu_pjrt_output_bytes(void* h, int i) {
  return ((ptpu_pjrt::Runner*)h)->out_raw.at(i).data();
}
// float32 view of output i (null + error when the output is not f32)
const float* ptpu_pjrt_output_data(void* h, int i) {
  auto* r = (ptpu_pjrt::Runner*)h;
  if (r->out_dtypes.at(i) != "float32") {
    ptpu_pjrt::g_err = "output " + std::to_string(i) + " is " +
                       r->out_dtypes.at(i) + ": use ptpu_pjrt_output_bytes";
    return nullptr;
  }
  return (const float*)r->out_raw.at(i).data();
}

void ptpu_pjrt_destroy(void* h) { delete (ptpu_pjrt::Runner*)h; }

}  // extern "C"
